"""Level-3 BLAS surface in JAX (paper §3: "all level-3 BLAS routines").

This is the dlsym-mode API: applications (or the interceptor) call these
functions directly; each routes through the active ``OffloadRuntime`` for
the offload decision, data placement and statistics, then executes
jit-compiled arithmetic. Real BLAS semantics are honoured: ``uplo``
triangles are the only parts of symmetric/triangular operands referenced,
``beta`` scaling, unit diagonals, side selection, and conjugate
transposes.

Precision prefix follows dtype: s/d/c/z for f32/f64/c64/c128 (bf16 maps
to the s-path on TPU). Leading batch dimensions select the batched
variants (cublas*Batched analogues) with the same placement logic.

Failure semantics: every call returns a correct result or raises.  A
transfer or kernel failure on the offload path is retried
(``SCILIB_RETRIES``) and, on exhaustion, the call re-executes on the
host path with the same operand values — bit-identical output, surfaced
as a ``fallback:<kind>`` decision and a trace event rather than a user
exception (:mod:`repro.core.faults`).  Only genuine bugs (type errors,
shape errors) propagate to the caller.
"""
from __future__ import annotations

import functools
import math
import operator
from typing import Callable, Dict, Hashable, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import runtime as rt
from repro.core.runtime import Tile, TileOp, TilePlan

__all__ = ["gemm", "symm", "hemm", "syrk", "herk", "syr2k", "her2k",
           "trmm", "trsm", "routine_name", "tensordot_flags"]

_RNAMES: Dict[Tuple[str, str], str] = {}


def routine_name(base: str, dtype) -> str:
    dt = jnp.dtype(dtype)
    name = _RNAMES.get((base, dt.name))
    if name is None:
        prefix = {"float32": "s", "float64": "d", "complex64": "c",
                  "complex128": "z", "bfloat16": "s", "float16": "s"}.get(
                      dt.name, "s")
        name = _RNAMES[(base, dt.name)] = prefix + base
    return name


# ----------------------------------------------------------------------- #
# dispatch fast path: memoized device scalars and bound kernels            #
#                                                                          #
# The seed runtime re-created ``jnp.asarray(alpha)`` device scalars and a  #
# fresh compute closure on *every* call (~50us per scalar on this          #
# container — dwarfing the 64^3 gemm it wraps).  Steady-state BLAS calls   #
# hit these tables instead and re-derive nothing; ``SCILIB_DISPATCH_CACHE  #
# =0`` restores the per-call re-derivation for A/B benchmarking.           #
# ----------------------------------------------------------------------- #
_CACHE_ON = True        # re-resolved at import (module bottom) and on
_SCALARS: Dict[Tuple, jax.Array] = {}   # every runtime construction
_BOUND: Dict[Hashable, Callable] = {}
_CACHE_LIMIT = 4096

# Context-local override of the cache flag (PR 7): concurrent sessions
# with different ``dispatch_cache`` settings each see their own flag.
# ``_CACHE_ON`` stays as the process-wide default/mirror — it is what
# sessionless threads fall back to, and what single-threaded callers
# introspect (the config tests assert on it directly).  The memo tables
# themselves stay shared: entries are pure functions of their keys, the
# dict writes are GIL-atomic, and a racing over-limit clear only costs
# a re-derivation.
from contextvars import ContextVar
_CACHE_VAR: ContextVar[Optional[bool]] = (
    ContextVar("scilib_dispatch_cache", default=None))


def refresh_cache_flag(enabled: Optional[bool] = None) -> None:
    """Sync the cache flag with the owning config's ``dispatch_cache``
    field (called from runtime construction / reconfigure).  With no
    argument, re-resolves through the config env boundary — the
    dlsym-mode path with no runtime installed.  Sets both the
    context-local flag (this session's threads) and the process mirror
    (sessionless fallback)."""
    global _CACHE_ON
    if enabled is None:
        from repro.core.config import OffloadConfig
        enabled = OffloadConfig.from_env().dispatch_cache
    _CACHE_VAR.set(bool(enabled))
    _CACHE_ON = bool(enabled)


def _cache_enabled() -> bool:
    v = _CACHE_VAR.get()
    return _CACHE_ON if v is None else v


def clear_caches() -> None:
    _SCALARS.clear()
    _BOUND.clear()


def _hashable(v):
    """Scalar cache key for alpha/beta, or None if uncacheable (arrays)."""
    if isinstance(v, (bool, int, float, complex)):
        return v
    return None


def _scalar(v, dtype) -> jax.Array:
    """Device scalar for alpha/beta, memoized by (value, dtype)."""
    key = _hashable(v)
    if not _cache_enabled() or key is None:
        return jnp.asarray(v, dtype=dtype)
    full = (key, jnp.dtype(dtype).name)
    arr = _SCALARS.get(full)
    if arr is None:
        if len(_SCALARS) > _CACHE_LIMIT:
            _SCALARS.clear()
        arr = _SCALARS[full] = jnp.asarray(v, dtype=dtype)
    return arr


def _bound(key: Optional[Hashable], factory: Callable[[], Callable]):
    """Memoize the bound compute closure for one call-site signature."""
    if not _cache_enabled() or key is None:
        return factory()
    fn = _BOUND.get(key)
    if fn is None:
        if len(_BOUND) > _CACHE_LIMIT:
            _BOUND.clear()
        fn = _BOUND[key] = factory()
    return fn


def _call_key(bkey: Optional[Hashable], m: int, n: int, k: int,
              batch: int) -> Optional[Hashable]:
    """Dispatch-cache key: (routine, flags, dtype, alpha, beta) + shape."""
    if bkey is None:
        return None
    return (bkey, m, n, k, batch)


def tensordot_flags(axes) -> Optional[Tuple[str, str]]:
    """Canonicalize a 2-D ``tensordot`` axes spec into gemm transpose
    flags, or None when the contraction is not gemm-shaped.

    For two matrices, a single contracted axis per operand is exactly a
    (possibly transposed) gemm — tensordot orders the output as (free
    axes of a, free axes of b), which is gemm's ``ik`` layout:

    ========================  ==========
    axes                      (ta, tb)
    ========================  ==========
    ``1`` / ``(1, 0)``        ``N, N``
    ``(0, 0)``                ``T, N``
    ``(1, 1)``                ``N, T``
    ``(0, 1)``                ``T, T``
    ========================  ==========

    ``axes=2`` (full double contraction -> scalar) and anything
    higher-rank are not level-3 calls and return None.
    """
    if isinstance(axes, int):
        if axes != 1:
            return None
        ax_a, ax_b = 1, 0              # a's last axis against b's first
    else:
        try:
            ax_a, ax_b = axes
        except (TypeError, ValueError):
            return None
        ax_a, ax_b = _single_axis(ax_a), _single_axis(ax_b)
        if ax_a is None or ax_b is None:
            return None
    ta = "N" if ax_a == 1 else "T"
    tb = "N" if ax_b == 0 else "T"
    return ta, tb


def _single_axis(ax) -> Optional[int]:
    """One matrix axis as a plain 0/1 int, or None.  Accepts ints,
    integer-likes (numpy scalars), and single-axis sequences."""
    if not isinstance(ax, int):
        try:                           # single-element sequence?
            if len(ax) != 1:
                return None
            ax = ax[0]
        except TypeError:
            pass                       # scalar-like: fall through
    try:
        ax = operator.index(ax)        # numpy integers included
    except TypeError:
        return None
    if ax not in (-2, -1, 0, 1):
        return None                    # out of range for a matrix
    return ax % 2                      # accept negative axes


def _op(x: jax.Array, trans: str) -> jax.Array:
    if trans == "N":
        return x
    xt = jnp.swapaxes(x, -1, -2)
    return jnp.conj(xt) if trans == "C" else xt


def _tri_mask(n: int, uplo: str, dtype=bool) -> jax.Array:
    r = jnp.arange(n)
    mask = r[:, None] >= r[None, :] if uplo == "L" else r[:, None] <= r[None, :]
    return mask


def _tri_ref(a: jax.Array, uplo: str, diag: str = "N") -> jax.Array:
    """The triangle of A that BLAS actually references."""
    n = a.shape[-1]
    t = jnp.tril(a) if uplo == "L" else jnp.triu(a)
    if diag == "U":
        eye = jnp.eye(n, dtype=a.dtype)
        t = t - t * eye + eye  # force unit diagonal
    return t


def _sym_full(a: jax.Array, uplo: str, conj: bool = False) -> jax.Array:
    """Materialize the full symmetric/hermitian matrix from one triangle."""
    n = a.shape[-1]
    tri = jnp.tril(a, -1) if uplo == "L" else jnp.triu(a, 1)
    other = jnp.swapaxes(tri, -1, -2)
    diag = jnp.diagonal(a, axis1=-2, axis2=-1)
    if conj:
        other = jnp.conj(other)
        diag = jnp.real(diag).astype(a.dtype)  # hermitian diag is real
    dmat = jnp.eye(n, dtype=a.dtype) * diag[..., :, None]
    return tri + other + dmat


def _batch_of(*arrays) -> int:
    b = 1
    for a in arrays:
        if a is not None and a.ndim > 2:
            b = int(functools.reduce(lambda x, y: x * y, a.shape[:-2], 1))
    return b


# ----------------------------------------------------------------------- #
# jitted arithmetic (shape-cached by jax)                                  #
# ----------------------------------------------------------------------- #
#: fp64 contractions longer than this are summed in chunks: a TPU runs
#: fp64 dot_general as multi-limb passes whose scratch is about 8x the
#: operands' bytes, so PARSEC's 93536-long contraction against its 1.8 GB
#: panel would need 15 GB of HBM in one piece (about 5 GB in chunks)
_WIDE_K_CHUNK = 8192


def _matmul(a, b):
    """``a @ b`` on the kernels' matmul, contraction-chunked for fp64."""
    from repro.kernels import ops as kops
    k = a.shape[-1]
    if a.dtype not in (jnp.float64, jnp.complex128) or k <= _WIDE_K_CHUNK:
        return kops.matmul(a, b)
    n = -(-k // _WIDE_K_CHUNK)
    edges = [k * i // n for i in range(n + 1)]
    out = None
    for k0, k1 in zip(edges, edges[1:]):
        part = kops.matmul(a[..., k0:k1], b[..., k0:k1, :])
        out = part if out is None else out + part
    return out


@functools.partial(jax.jit, static_argnames=("trans_a", "trans_b", "has_c"))
def _gemm_kernel(a, b, c, alpha, beta, *, trans_a, trans_b, has_c):
    acc = _matmul(_op(a, trans_a), _op(b, trans_b))
    out = alpha.astype(acc.dtype) * acc
    if has_c:
        out = out + beta.astype(acc.dtype) * c
    return out.astype(a.dtype)


@functools.partial(jax.jit, static_argnames=("side", "uplo", "conj", "has_c"))
def _symm_kernel(a, b, c, alpha, beta, *, side, uplo, conj, has_c):
    from repro.kernels import ops as kops
    full = _sym_full(a, uplo, conj=conj)
    acc = kops.matmul(full, b) if side == "L" else kops.matmul(b, full)
    out = alpha.astype(acc.dtype) * acc
    if has_c:
        out = out + beta.astype(acc.dtype) * c
    return out.astype(b.dtype)


@functools.partial(jax.jit,
                   static_argnames=("uplo", "trans", "conj", "has_c"))
def _syrk_kernel(a, c, alpha, beta, *, uplo, trans, conj, has_c):
    from repro.kernels import ops as kops
    opa = _op(a, trans)
    at = jnp.swapaxes(opa, -1, -2)
    if conj:
        at = jnp.conj(at)
    acc = kops.matmul(opa, at)
    upd = alpha.astype(acc.dtype) * acc
    n = upd.shape[-1]
    mask = _tri_mask(n, uplo)
    if has_c:
        tri = jnp.where(mask, upd + beta.astype(acc.dtype) * c, c)
    else:
        tri = jnp.where(mask, upd, jnp.zeros_like(upd))
    return tri.astype(a.dtype)


@functools.partial(jax.jit,
                   static_argnames=("uplo", "trans", "conj", "has_c"))
def _syr2k_kernel(a, b, c, alpha, beta, *, uplo, trans, conj, has_c):
    from repro.kernels import ops as kops
    opa, opb = _op(a, trans), _op(b, trans)
    bt, at = jnp.swapaxes(opb, -1, -2), jnp.swapaxes(opa, -1, -2)
    if conj:
        # her2k: C := alpha A B^H + conj(alpha) B A^H + beta C
        bt, at = jnp.conj(bt), jnp.conj(at)
        al = alpha.astype(opa.dtype)
        upd = al * kops.matmul(opa, bt) + jnp.conj(al) * kops.matmul(opb, at)
    else:
        acc = kops.matmul(opa, bt) + kops.matmul(opb, at)
        upd = alpha.astype(acc.dtype) * acc
    n = upd.shape[-1]
    mask = _tri_mask(n, uplo)
    if has_c:
        # upd.dtype, not acc.dtype: the her2k branch above never binds
        # acc, and referencing it crashed every her2k call with a C
        tri = jnp.where(mask, upd + beta.astype(upd.dtype) * c, c)
    else:
        tri = jnp.where(mask, upd, jnp.zeros_like(upd))
    return tri.astype(a.dtype)


@functools.partial(jax.jit, static_argnames=("trans", "conj", "has_c"))
def _syrk_block_kernel(ai, aj, c, alpha, beta, *, trans, conj, has_c):
    """Off-diagonal block of a tiled syrk/herk:
    C[i,j] := alpha op(A)_i op(A)_j^{T|H} + beta C[i,j] (full block)."""
    from repro.kernels import ops as kops
    opi, opj = _op(ai, trans), _op(aj, trans)
    jt = jnp.swapaxes(opj, -1, -2)
    if conj:
        jt = jnp.conj(jt)
    acc = kops.matmul(opi, jt)
    out = alpha.astype(acc.dtype) * acc
    if has_c:
        out = out + beta.astype(acc.dtype) * c
    return out.astype(ai.dtype)


@functools.partial(jax.jit, static_argnames=("trans", "conj", "has_c"))
def _syr2k_block_kernel(ai, bi, aj, bj, c, alpha, beta, *, trans, conj,
                        has_c):
    """Off-diagonal block of a tiled syr2k/her2k — the two-term rank-2k
    analogue of :func:`_syrk_block_kernel`:

    C[i,j] := alpha op(A)_i op(B)_j^T + alpha op(B)_i op(A)_j^T + beta C
    (her2k conjugate-transposes and uses conj(alpha) on the second term).
    """
    from repro.kernels import ops as kops
    opai, opbi = _op(ai, trans), _op(bi, trans)
    bjt = jnp.swapaxes(_op(bj, trans), -1, -2)
    ajt = jnp.swapaxes(_op(aj, trans), -1, -2)
    if conj:
        bjt, ajt = jnp.conj(bjt), jnp.conj(ajt)
        al = alpha.astype(opai.dtype)
        acc = (al * kops.matmul(opai, bjt)
               + jnp.conj(al) * kops.matmul(opbi, ajt))
    else:
        acc = kops.matmul(opai, bjt) + kops.matmul(opbi, ajt)
        acc = alpha.astype(acc.dtype) * acc
    out = acc
    if has_c:
        out = out + beta.astype(acc.dtype) * c
    return out.astype(ai.dtype)


@functools.partial(jax.jit,
                   static_argnames=("side", "uplo", "trans", "diag"))
def _trmm_kernel(a, b, alpha, *, side, uplo, trans, diag):
    from repro.kernels import ops as kops
    tri = _tri_ref(a, uplo, diag)
    tri = _op(tri, trans)
    out = kops.matmul(tri, b) if side == "L" else kops.matmul(b, tri)
    return (alpha.astype(out.dtype) * out).astype(b.dtype)


@functools.partial(jax.jit,
                   static_argnames=("side", "uplo", "trans", "diag"))
def _trsm_kernel(a, b, alpha, *, side, uplo, trans, diag):
    from repro.kernels import ops as kops
    rhs = alpha.astype(b.dtype) * b
    return kops.trsm(a, rhs, side=side, uplo=uplo, trans=trans,
                     diag=diag).astype(b.dtype)


# ----------------------------------------------------------------------- #
# pallas-venue arithmetic (OffloadConfig.kernel_path / SCILIB_KERNELS)     #
#                                                                          #
# Mirrors of the jitted kernels above that route the inner product         #
# through the hand-written kernels (``kops.kernel_*``) instead of the      #
# generic XLA formulation.  These closures are built only when the kernel  #
# path is on and the routine has a kernel (``kops.kernel_available``), so  #
# default-off runs never trace — or even import — any of this.  The        #
# ``_*_klean`` variants serve the dominant alpha=1 / beta=0 / no-C call    #
# shape with no scalar epilogue at all: fewer jit arguments and no         #
# multiply, which is the venue's measurable edge on backends where         #
# ``kernel_*`` itself degrades to the same XLA matmul.                     #
# ----------------------------------------------------------------------- #
_KOPS = None


def _kops():
    """repro.kernels.ops, imported on first kernel-path use only (the
    default pipeline keeps its import graph unchanged)."""
    global _KOPS
    if _KOPS is None:
        from repro.kernels import ops
        _KOPS = ops
    return _KOPS


def _kernel_path_active() -> bool:
    runtime = rt.active()
    return runtime is not None and runtime.kernel_path


def _kernel_block() -> int:
    runtime = rt.active()
    return runtime.kernel_block if runtime is not None else 0


@functools.partial(jax.jit, static_argnames=("block",))
def _gemm_klean(a, b, *, block):
    from repro.kernels import ops as kops
    return kops.kernel_matmul(a, b, block=block)


@functools.partial(jax.jit, static_argnames=(
    "trans_a", "trans_b", "has_c", "block"))
def _gemm_kvenue(a, b, c, alpha, beta, *, trans_a, trans_b, has_c, block):
    from repro.kernels import ops as kops
    acc = kops.kernel_matmul(_op(a, trans_a), _op(b, trans_b), block=block)
    out = alpha.astype(acc.dtype) * acc
    if has_c:
        out = out + beta.astype(acc.dtype) * c
    return out.astype(a.dtype)


@functools.partial(jax.jit, static_argnames=("uplo", "trans", "block"))
def _syrk_klean(a, *, uplo, trans, block):
    from repro.kernels import ops as kops
    # real syrk only reaches the venue (kernel_available), so "C" == "T"
    t = "N" if trans == "N" else "T"
    return kops.kernel_syrk(a, uplo=uplo, trans=t, block=block)


@functools.partial(jax.jit, static_argnames=(
    "uplo", "trans", "conj", "has_c", "block"))
def _syrk_kvenue(a, c, alpha, beta, *, uplo, trans, conj, has_c, block):
    from repro.kernels import ops as kops
    opa = _op(a, trans)
    at = jnp.swapaxes(opa, -1, -2)
    if conj:
        at = jnp.conj(at)
    acc = kops.kernel_matmul(opa, at, block=block)
    upd = alpha.astype(acc.dtype) * acc
    n = upd.shape[-1]
    mask = _tri_mask(n, uplo)
    if has_c:
        tri = jnp.where(mask, upd + beta.astype(acc.dtype) * c, c)
    else:
        tri = jnp.where(mask, upd, jnp.zeros_like(upd))
    return tri.astype(a.dtype)


@functools.partial(jax.jit, static_argnames=(
    "trans", "conj", "has_c", "block"))
def _syrk_block_kvenue(ai, aj, c, alpha, beta, *, trans, conj, has_c,
                       block):
    from repro.kernels import ops as kops
    opi, opj = _op(ai, trans), _op(aj, trans)
    jt = jnp.swapaxes(opj, -1, -2)
    if conj:
        jt = jnp.conj(jt)
    acc = kops.kernel_matmul(opi, jt, block=block)
    out = alpha.astype(acc.dtype) * acc
    if has_c:
        out = out + beta.astype(acc.dtype) * c
    return out.astype(ai.dtype)


@functools.partial(jax.jit, static_argnames=(
    "side", "uplo", "trans", "diag", "block"))
def _trsm_klean(a, b, *, side, uplo, trans, diag, block):
    from repro.kernels import ops as kops
    return kops.kernel_trsm(a, b, side=side, uplo=uplo, trans=trans,
                            diag=diag, block=block).astype(b.dtype)


@functools.partial(jax.jit, static_argnames=(
    "side", "uplo", "trans", "diag", "block"))
def _trsm_kvenue(a, b, alpha, *, side, uplo, trans, diag, block):
    from repro.kernels import ops as kops
    rhs = alpha.astype(b.dtype) * b
    return kops.kernel_trsm(a, rhs, side=side, uplo=uplo, trans=trans,
                            diag=diag, block=block).astype(b.dtype)


# ----------------------------------------------------------------------- #
# split-precision arithmetic (OffloadConfig.precision / SCILIB_PRECISION)  #
#                                                                          #
# Twins of the jitted kernels above that run the fp64 inner product as     #
# split low-precision slice passes (repro.core.precision) instead of       #
# native dgemm.  Like the pallas-venue closures, these are built only      #
# when a split scheme is configured and the base/dtype supports one        #
# (real 2-D fp64), so default-off runs never trace — or import — any of   #
# it.  Each builder is memoized per (scheme, venue, block): the xla        #
# venue runs the plain fp32 XLA matmul per pass, the pallas venue the     #
# fp32 Pallas GEMM kernel (repro.kernels.split_gemm) — which is the       #
# only fp64 path that venue has.                                           #
# ----------------------------------------------------------------------- #
def _split_mm(venue: str, block: int):
    """The fp32 pass primitive for one venue (None = precision module
    default, the XLA fp32 matmul)."""
    if venue == "pallas":
        from repro.kernels import split_gemm
        return split_gemm.pass_mm(block)
    return None


def _split_gemm_kernel(scheme, venue, block):
    """Jitted gemm-shaped split kernel, memoized per (scheme, venue,
    block) — the split twin of :func:`_gemm_kernel`."""
    def build():
        from repro.core import precision as prc
        mm = _split_mm(venue, block)

        @functools.partial(jax.jit,
                           static_argnames=("trans_a", "trans_b", "has_c"))
        def kern(a, b, c, alpha, beta, *, trans_a, trans_b, has_c):
            acc = prc.matmul(_op(a, trans_a), _op(b, trans_b), scheme,
                             mm=mm)
            out = alpha.astype(acc.dtype) * acc
            if has_c:
                out = out + beta.astype(acc.dtype) * c
            return out.astype(a.dtype)
        return kern
    return _bound(("splitk", "gemm", scheme, venue, block), build)


def _split_syrk_kernel(scheme, venue, block):
    """Split twin of :func:`_syrk_kernel` (real fp64 only, so no conj)."""
    def build():
        from repro.core import precision as prc
        mm = _split_mm(venue, block)

        @functools.partial(jax.jit,
                           static_argnames=("uplo", "trans", "has_c"))
        def kern(a, c, alpha, beta, *, uplo, trans, has_c):
            opa = _op(a, trans)
            acc = prc.matmul(opa, jnp.swapaxes(opa, -1, -2), scheme,
                             mm=mm)
            upd = alpha.astype(acc.dtype) * acc
            mask = _tri_mask(upd.shape[-1], uplo)
            if has_c:
                tri = jnp.where(mask, upd + beta.astype(acc.dtype) * c, c)
            else:
                tri = jnp.where(mask, upd, jnp.zeros_like(upd))
            return tri.astype(a.dtype)
        return kern
    return _bound(("splitk", "syrk", scheme, venue, block), build)


def _split_syrk_block_kernel(scheme, venue, block):
    """Split twin of :func:`_syrk_block_kernel` (tiled syrk off-diagonal
    blocks are gemm-shaped)."""
    def build():
        from repro.core import precision as prc
        mm = _split_mm(venue, block)

        @functools.partial(jax.jit, static_argnames=("trans", "has_c"))
        def kern(ai, aj, c, alpha, beta, *, trans, has_c):
            opi, opj = _op(ai, trans), _op(aj, trans)
            acc = prc.matmul(opi, jnp.swapaxes(opj, -1, -2), scheme,
                             mm=mm)
            out = alpha.astype(acc.dtype) * acc
            if has_c:
                out = out + beta.astype(acc.dtype) * c
            return out.astype(ai.dtype)
        return kern
    return _bound(("splitk", "syrkb", scheme, venue, block), build)


def _split_trsm_kernel(scheme, venue, block):
    """Split twin of :func:`_trsm_kernel`: fp32 solve + one refinement
    step whose residual runs the split matmul.  The referenced triangle
    is materialized first — the refinement's ``op(A) X`` product reads
    the full array, unlike the solves."""
    def build():
        from repro.core import precision as prc
        mm = _split_mm(venue, block)

        @functools.partial(jax.jit,
                           static_argnames=("side", "uplo", "trans",
                                            "diag"))
        def kern(a, b, alpha, *, side, uplo, trans, diag):
            rhs = alpha.astype(b.dtype) * b
            tri = _tri_ref(a, uplo, diag)
            return prc.trsm(tri, rhs, scheme,
                            left_side=(side == "L"),
                            lower=(uplo == "L"),
                            trans_a=(trans != "N"),
                            unit_diag=(diag == "U"),
                            mm=mm).astype(b.dtype)
        return kern
    return _bound(("splitk", "trsm", scheme, venue, block), build)


def _split_bound(base, dt, bkey, sfactory, flat2d=True):
    """The split-precision twin of ``_kernel_bound``: build the
    ``(scheme, venue) -> compute`` factory the runtime's precision
    stage consults, or None when no split scheme is configured or the
    base/dtype/shape has no split formulation (real 2-D fp64 only —
    batched calls stay native).  Memo keys get a ``"split"`` prefix
    plus scheme/venue/block so split closures never collide with the
    XLA or pallas-venue ones in ``_BOUND``."""
    runtime = rt.active()
    if runtime is None or not runtime.precision or not flat2d:
        return None
    from repro.core import precision as prc
    if not prc.supported(base, dt):
        return None
    block = _kernel_block()

    def split_compute(scheme, venue):
        venue = venue or "xla"
        skey = (("split", scheme, venue, block) + bkey
                if bkey is not None else None)
        return _bound(skey,
                      functools.partial(sfactory, scheme, venue, block))
    return split_compute


# ----------------------------------------------------------------------- #
# multi-device tile decomposition (BLASX-style 2-D sharding)               #
#                                                                          #
# When the runtime sees more than one device tier, super-threshold calls   #
# are split into tiles the scheduler deals round-robin-with-affinity       #
# across devices.  The decomposition is per-routine: gemm tiles the        #
# output 2-D; symm/trmm/trsm split the rectangular panel along its free    #
# dimension (the triangle replicates); syrk/herk tile the stored triangle  #
# of C by block, diagonal blocks through the syrk kernel, off-diagonal     #
# through a gemm-shaped block kernel; syr2k/her2k ride the same triangle   #
# grid with a two-term block kernel (the last level-3 gap closed).         #
# Builders return None when the matrix is too small to split               #
# (``SCILIB_TILE_MIN``), which falls back to the single-device path.       #
# ----------------------------------------------------------------------- #
def _tile_min() -> int:
    """Minimum tile edge, from the active runtime's config (the
    ``tile_min`` field replacing ``SCILIB_TILE_MIN``)."""
    runtime = rt.active()
    return runtime.config.tile_min if runtime is not None else 64


def _shard_active(batch: int, *arrays) -> bool:
    """Tile decomposition applies to plain 2-D calls only: a leading
    batch axis — even a singleton one — uses the batched kernels, whose
    axes the 2-D tile coordinates do not address."""
    runtime = rt.active()
    if runtime is None or batch != 1 or runtime.n_devices < 2:
        return False
    return all(x is None or x.ndim == 2 for x in arrays)


def _splits(dim: int, g: int) -> List[Tuple[int, int]]:
    """g contiguous block ranges covering [0, dim)."""
    base, rem = divmod(dim, g)
    edges = [0]
    for i in range(g):
        edges.append(edges[-1] + base + (1 if i < rem else 0))
    return [(edges[i], edges[i + 1]) for i in range(g)]


def _grid2d(n_dev: int, m: int, n: int) -> Optional[Tuple[int, int]]:
    """Near-square tile grid with >= n_dev tiles, clamped so no tile edge
    drops under the minimum; None when the call is too small to shard."""
    min_tile = _tile_min()
    gm = max(1, math.isqrt(n_dev))
    gn = -(-n_dev // gm)
    if n < m:                       # split the longer dimension more finely
        gm, gn = gn, gm
    gm = min(gm, max(1, m // min_tile))
    gn = min(gn, max(1, n // min_tile))
    if gm * gn < 2:
        return None
    return gm, gn


def _grid1d(n_dev: int, dim: int) -> Optional[int]:
    g = min(n_dev, max(1, dim // _tile_min()))
    return g if g >= 2 else None


def _assemble(blocks: List[List[jax.Array]]) -> jax.Array:
    rows = [row[0] if len(row) == 1 else jnp.concatenate(row, axis=-1)
            for row in blocks]
    return rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=-2)


def _full_coords(x: jax.Array) -> Tuple[int, int, int, int]:
    return (0, x.shape[-2], 0, x.shape[-1])


def _rowblock_coords(x: jax.Array, trans: str,
                     r0: int, r1: int) -> Tuple[int, int, int, int]:
    """Coords on the parent for row block [r0:r1) of op(x)."""
    if trans == "N":
        return (r0, r1, 0, x.shape[-1])
    return (0, x.shape[-2], r0, r1)


def _colblock_coords(x: jax.Array, trans: str,
                     c0: int, c1: int) -> Tuple[int, int, int, int]:
    """Coords on the parent for column block [c0:c1) of op(x)."""
    if trans == "N":
        return (0, x.shape[-2], c0, c1)
    return (c0, c1, 0, x.shape[-1])


def _shard_gemm(a, b, c, alpha, beta, trans_a, trans_b,
                n_dev, venue="xla", precision="") -> Optional[TilePlan]:
    m = a.shape[-2] if trans_a == "N" else a.shape[-1]
    n = b.shape[-1] if trans_b == "N" else b.shape[-2]
    g = _grid2d(n_dev, m, n)
    if g is None:
        return None
    gm, gn = g
    rows, cols = _splits(m, gm), _splits(n, gn)
    dt = a.dtype
    has_c = c is not None
    alpha_, beta_ = _scalar(alpha, dt), _scalar(beta, dt)
    # pallas venue: every tile runs the kernel-backed block arithmetic;
    # a split decision swaps in the split tile kernel the same way
    if precision:
        gemm_k = _split_gemm_kernel(precision, venue, _kernel_block())
    elif venue == "pallas":
        gemm_k = functools.partial(_gemm_kvenue, block=_kernel_block())
    else:
        gemm_k = _gemm_kernel
    if has_c:
        def tile_fn(a_, b_, c_):
            return gemm_k(a_, b_, c_, alpha_, beta_, trans_a=trans_a,
                          trans_b=trans_b, has_c=True)
    else:
        czero = _scalar(0.0, dt)

        def tile_fn(a_, b_):
            return gemm_k(a_, b_, czero, alpha_, beta_,
                          trans_a=trans_a, trans_b=trans_b,
                          has_c=False)
    tiles = []
    for (r0, r1) in rows:
        for (q0, q1) in cols:
            ops = [TileOp("A", a, _rowblock_coords(a, trans_a, r0, r1),
                          shared=(gm == 1)),
                   TileOp("B", b, _colblock_coords(b, trans_b, q0, q1),
                          shared=(gn == 1))]
            if has_c:
                ops.append(TileOp("C", c, (r0, r1, q0, q1), written=True))
            tiles.append(Tile(tuple(ops), tile_fn, (r0, r1, q0, q1)))

    def gather(outs):
        it = iter(outs)
        return _assemble([[next(it) for _ in cols] for _ in rows])

    return TilePlan((gm, gn), tuple(tiles), gather)


def _shard_symm(a, b, c, alpha, beta, side, uplo, conj,
                n_dev) -> Optional[TilePlan]:
    m, n = b.shape[-2], b.shape[-1]
    dim = n if side == "L" else m
    g = _grid1d(n_dev, dim)
    if g is None:
        return None
    panels = _splits(dim, g)
    dt = b.dtype
    has_c = c is not None
    alpha_, beta_ = _scalar(alpha, dt), _scalar(beta, dt)
    if has_c:
        def tile_fn(a_, b_, c_):
            return _symm_kernel(a_, b_, c_, alpha_, beta_, side=side,
                                uplo=uplo, conj=conj, has_c=True)
    else:
        czero = _scalar(0.0, dt)

        def tile_fn(a_, b_):
            return _symm_kernel(a_, b_, czero, alpha_, beta_, side=side,
                                uplo=uplo, conj=conj, has_c=False)
    tiles = []
    for (p0, p1) in panels:
        coords = (0, m, p0, p1) if side == "L" else (p0, p1, 0, n)
        ops = [TileOp("A", a, _full_coords(a), shared=True),
               TileOp("B", b, coords)]
        if has_c:
            ops.append(TileOp("C", c, coords, written=True))
        tiles.append(Tile(tuple(ops), tile_fn, coords))

    def gather(outs):
        return jnp.concatenate(outs, axis=-1 if side == "L" else -2)

    return TilePlan((1, g) if side == "L" else (g, 1), tuple(tiles), gather)


def _shard_syrk(a, c, alpha, beta, uplo, trans, conj,
                n_dev, venue="xla", precision="") -> Optional[TilePlan]:
    n = a.shape[-2] if trans == "N" else a.shape[-1]
    g = 2
    while g * (g + 1) // 2 < n_dev:
        g += 1
    g = min(g, max(1, n // _tile_min()))
    if g < 2:
        return None
    blocks = _splits(n, g)
    dt = a.dtype
    has_c = c is not None
    alpha_, beta_ = _scalar(alpha, dt), _scalar(beta, dt)
    czero = _scalar(0.0, dt)
    if precision:
        blk = _kernel_block()
        # real fp64 only reaches the split path, so conj never applies
        sk = _split_syrk_kernel(precision, venue, blk)
        sbk = _split_syrk_block_kernel(precision, venue, blk)

        def syrk_k(a_, c_, al, be, *, uplo, trans, conj, has_c):
            return sk(a_, c_, al, be, uplo=uplo, trans=trans,
                      has_c=has_c)

        def syrk_block_k(ai, aj, c_, al, be, *, trans, conj, has_c):
            return sbk(ai, aj, c_, al, be, trans=trans, has_c=has_c)
    elif venue == "pallas":
        blk = _kernel_block()
        syrk_k = functools.partial(_syrk_kvenue, block=blk)
        syrk_block_k = functools.partial(_syrk_block_kvenue, block=blk)
    else:
        syrk_k, syrk_block_k = _syrk_kernel, _syrk_block_kernel
    if has_c:
        def diag_fn(a_, c_):
            return syrk_k(a_, c_, alpha_, beta_, uplo=uplo,
                          trans=trans, conj=conj, has_c=True)

        def off_fn(ai, aj, cij):
            return syrk_block_k(ai, aj, cij, alpha_, beta_,
                                trans=trans, conj=conj, has_c=True)
    else:
        def diag_fn(a_):
            return syrk_k(a_, czero, alpha_, beta_, uplo=uplo,
                          trans=trans, conj=conj, has_c=False)

        def off_fn(ai, aj):
            return syrk_block_k(ai, aj, czero, alpha_, beta_,
                                trans=trans, conj=conj, has_c=False)
    tiles, stored = [], {}
    for i in range(g):
        for j in range(g):
            if not (i >= j if uplo == "L" else i <= j):
                continue
            (r0, r1), (q0, q1) = blocks[i], blocks[j]
            coords = (r0, r1, q0, q1)
            if i == j:
                ops = [TileOp("A", a, _rowblock_coords(a, trans, r0, r1))]
                fn = diag_fn
            else:
                ops = [TileOp("A", a, _rowblock_coords(a, trans, r0, r1)),
                       TileOp("A", a, _rowblock_coords(a, trans, q0, q1))]
                fn = off_fn
            if has_c:
                ops.append(TileOp("C", c, coords, written=True))
            stored[(i, j)] = len(tiles)
            tiles.append(Tile(tuple(ops), fn, coords))

    def gather(outs):
        grid = []
        for i in range(g):
            row = []
            for j in range(g):
                idx = stored.get((i, j))
                if idx is not None:
                    row.append(outs[idx])
                    continue
                (r0, r1), (q0, q1) = blocks[i], blocks[j]
                if has_c:          # untouched triangle keeps C verbatim
                    row.append(c[r0:r1, q0:q1].astype(dt))
                else:
                    row.append(jnp.zeros((r1 - r0, q1 - q0), dt))
            grid.append(row)
        return _assemble(grid)

    return TilePlan((g, g), tuple(tiles), gather)


def _shard_syr2k(a, b, c, alpha, beta, uplo, trans, conj,
                 n_dev) -> Optional[TilePlan]:
    """syr2k/her2k on the syrk triangle grid: the stored triangle of C
    tiles by block — diagonal blocks run the full rank-2k kernel on the
    matching op-row blocks of A and B, off-diagonal blocks the two-term
    block kernel.  A and B row blocks steer affinity exactly like syrk's
    single operand (each block appears in one grid row and one column)."""
    n = a.shape[-2] if trans == "N" else a.shape[-1]
    g = 2
    while g * (g + 1) // 2 < n_dev:
        g += 1
    g = min(g, max(1, n // _tile_min()))
    if g < 2:
        return None
    blocks = _splits(n, g)
    dt = a.dtype
    has_c = c is not None
    alpha_, beta_ = _scalar(alpha, dt), _scalar(beta, dt)
    czero = _scalar(0.0, dt)
    if has_c:
        def diag_fn(a_, b_, c_):
            return _syr2k_kernel(a_, b_, c_, alpha_, beta_, uplo=uplo,
                                 trans=trans, conj=conj, has_c=True)

        def off_fn(ai, bi, aj, bj, cij):
            return _syr2k_block_kernel(ai, bi, aj, bj, cij, alpha_, beta_,
                                       trans=trans, conj=conj, has_c=True)
    else:
        def diag_fn(a_, b_):
            return _syr2k_kernel(a_, b_, czero, alpha_, beta_, uplo=uplo,
                                 trans=trans, conj=conj, has_c=False)

        def off_fn(ai, bi, aj, bj):
            return _syr2k_block_kernel(ai, bi, aj, bj, czero, alpha_,
                                       beta_, trans=trans, conj=conj,
                                       has_c=False)
    tiles, stored = [], {}
    for i in range(g):
        for j in range(g):
            if not (i >= j if uplo == "L" else i <= j):
                continue
            (r0, r1), (q0, q1) = blocks[i], blocks[j]
            coords = (r0, r1, q0, q1)
            if i == j:
                ops = [TileOp("A", a, _rowblock_coords(a, trans, r0, r1)),
                       TileOp("B", b, _rowblock_coords(b, trans, r0, r1))]
                fn = diag_fn
            else:
                ops = [TileOp("A", a, _rowblock_coords(a, trans, r0, r1)),
                       TileOp("B", b, _rowblock_coords(b, trans, r0, r1)),
                       TileOp("A", a, _rowblock_coords(a, trans, q0, q1)),
                       TileOp("B", b, _rowblock_coords(b, trans, q0, q1))]
                fn = off_fn
            if has_c:
                ops.append(TileOp("C", c, coords, written=True))
            stored[(i, j)] = len(tiles)
            tiles.append(Tile(tuple(ops), fn, coords))

    def gather(outs):
        grid = []
        for i in range(g):
            row = []
            for j in range(g):
                idx = stored.get((i, j))
                if idx is not None:
                    row.append(outs[idx])
                    continue
                (r0, r1), (q0, q1) = blocks[i], blocks[j]
                if has_c:          # untouched triangle keeps C verbatim
                    row.append(c[r0:r1, q0:q1].astype(dt))
                else:
                    row.append(jnp.zeros((r1 - r0, q1 - q0), dt))
            grid.append(row)
        return _assemble(grid)

    return TilePlan((g, g), tuple(tiles), gather)


def _shard_tri(a, b, side, uplo, trans, diag, alpha, kernel,
               n_dev, venue="xla", precision="") -> Optional[TilePlan]:
    """trmm/trsm: the RHS panel splits along its free dimension; each
    panel solve/multiply is independent, the triangle replicates."""
    m, n = b.shape[-2], b.shape[-1]
    dim = n if side == "L" else m
    g = _grid1d(n_dev, dim)
    if g is None:
        return None
    panels = _splits(dim, g)
    dt = b.dtype
    alpha_ = _scalar(alpha, dt)
    if precision and kernel is _trsm_kernel:
        # split trsm panels: same geometry, refined fp32 panel solves
        kernel = _split_trsm_kernel(precision, venue, _kernel_block())
    elif venue == "pallas" and kernel is _trsm_kernel:
        # only trsm has a kernel; trmm never resolves to the pallas venue
        kernel = functools.partial(_trsm_kvenue, block=_kernel_block())

    def tile_fn(a_, b_):
        return kernel(a_, b_, alpha_, side=side, uplo=uplo, trans=trans,
                      diag=diag)

    tiles = []
    for (p0, p1) in panels:
        coords = (0, m, p0, p1) if side == "L" else (p0, p1, 0, n)
        tiles.append(Tile((TileOp("A", a, _full_coords(a), shared=True),
                           TileOp("B", b, coords, written=True)),
                          tile_fn, coords))

    def gather(outs):
        return jnp.concatenate(outs, axis=-1 if side == "L" else -2)

    return TilePlan((1, g) if side == "L" else (g, 1), tuple(tiles), gather)


# ----------------------------------------------------------------------- #
# public routines                                                          #
# ----------------------------------------------------------------------- #
def _dispatch(routine, m, n, k, operands, compute, batch=1, key=None,
              shard=None, kernel_compute=None, split_compute=None,
              split_check=None):
    runtime = rt.active()
    if runtime is None:
        return compute(*[x for _, x, _, _ in operands])
    return runtime.blas_call(routine, m, n, k, operands, compute,
                             batch=batch, key=key, shard=shard,
                             kernel_compute=kernel_compute,
                             split_compute=split_compute,
                             split_check=split_check)


def _kernel_bound(base, dt, bkey, kfactory):
    """The pallas-venue twin of ``_bound``: build (or recall) the
    kernel-backed compute closure for one call-site signature, or None
    when the kernel path is off or the routine/dtype has no kernel.
    Memo keys get a ``"kern"`` prefix plus the block edge so venue
    closures never collide with the XLA ones in ``_BOUND``."""
    if not _kernel_path_active() or not _kops().kernel_available(base, dt):
        return None
    block = _kernel_block()
    kkey = ("kern", block) + bkey if bkey is not None else None
    return _bound(kkey, functools.partial(kfactory, block))


def gemm(a: jax.Array, b: jax.Array, c: Optional[jax.Array] = None, *,
         alpha=1.0, beta=0.0, trans_a: str = "N",
         trans_b: str = "N") -> jax.Array:
    """C := alpha op(A) op(B) + beta C (the paper's headline routine)."""
    opm = a.shape[-2] if trans_a == "N" else a.shape[-1]
    opk = a.shape[-1] if trans_a == "N" else a.shape[-2]
    opn = b.shape[-1] if trans_b == "N" else b.shape[-2]
    batch = _batch_of(a, b, c)
    dt = a.dtype
    has_c = c is not None
    av, bv = _hashable(alpha), _hashable(beta)
    bkey = (("gemm", dt.name, trans_a, trans_b, has_c, av, bv)
            if av is not None and bv is not None else None)

    def factory():
        alpha_ = _scalar(alpha, dt)
        beta_ = _scalar(beta, dt)
        if has_c:
            def compute(a_, b_, c_):
                return _gemm_kernel(a_, b_, c_, alpha_, beta_,
                                    trans_a=trans_a, trans_b=trans_b,
                                    has_c=True)
        else:
            c0 = _scalar(0.0, dt)

            def compute(a_, b_):
                return _gemm_kernel(a_, b_, c0, alpha_, beta_,
                                    trans_a=trans_a, trans_b=trans_b,
                                    has_c=False)
        return compute

    def kfactory(block):
        if (not has_c and av == 1 and bv == 0
                and trans_a == "N" and trans_b == "N"):
            def kcompute(a_, b_):          # lean: no scalar epilogue
                return _gemm_klean(a_, b_, block=block)
            return kcompute
        alpha_ = _scalar(alpha, dt)
        beta_ = _scalar(beta, dt)
        if has_c:
            def kcompute(a_, b_, c_):
                return _gemm_kvenue(a_, b_, c_, alpha_, beta_,
                                    trans_a=trans_a, trans_b=trans_b,
                                    has_c=True, block=block)
        else:
            c0 = _scalar(0.0, dt)

            def kcompute(a_, b_):
                return _gemm_kvenue(a_, b_, c0, alpha_, beta_,
                                    trans_a=trans_a, trans_b=trans_b,
                                    has_c=False, block=block)
        return kcompute

    def sfactory(scheme, venue, block):
        kern = _split_gemm_kernel(scheme, venue, block)
        alpha_ = _scalar(alpha, dt)
        beta_ = _scalar(beta, dt)
        if has_c:
            def scompute(a_, b_, c_):
                return kern(a_, b_, c_, alpha_, beta_, trans_a=trans_a,
                            trans_b=trans_b, has_c=True)
        else:
            c0 = _scalar(0.0, dt)

            def scompute(a_, b_):
                return kern(a_, b_, c0, alpha_, beta_, trans_a=trans_a,
                            trans_b=trans_b, has_c=False)
        return scompute

    compute = _bound(bkey, factory)
    kernel_compute = _kernel_bound("gemm", dt, bkey, kfactory)
    flat2d = (a.ndim == 2 and b.ndim == 2
              and (c is None or c.ndim == 2))
    split_compute = _split_bound("gemm", dt, bkey, sfactory, flat2d)
    split_check = None
    if split_compute is not None:
        from repro.core import precision as prc

        def split_check(out, a_, b_, c_=None):
            return prc.gemm_residual(out, _op(a_, trans_a),
                                     _op(b_, trans_b), c_, alpha, beta)
    ops = [("A", a, float(opn), False), ("B", b, float(opm), False)]
    if has_c:
        ops.append(("C", c, 1.0, True))
    shard = (functools.partial(_shard_gemm, a, b, c, alpha, beta,
                               trans_a, trans_b)
             if _shard_active(batch, a, b, c) else None)
    return _dispatch(routine_name("gemm", dt), opm, opn, opk,
                     ops, compute, batch,
                     key=_call_key(bkey, opm, opn, opk, batch),
                     shard=shard, kernel_compute=kernel_compute,
                     split_compute=split_compute,
                     split_check=split_check)


@jax.jit
def _gemv_kernel_n(a, x):
    return a @ x


@jax.jit
def _gemv_kernel_t(a, x):
    return jnp.swapaxes(a, -1, -2) @ x


def gemv(a: jax.Array, x: jax.Array, *, trans: str = "N") -> jax.Array:
    """y := op(A) x — the matrix-vector (level-2) interception surface.

    The paper's tool intercepts level-3 BLAS; matrix-vector products
    used to bypass interception entirely and vanish from the report.
    They are now recorded and counted as gemv-shaped calls and routed
    through the same dispatch pipeline with the ordinary threshold
    rule: N_avg = (m*n)^(1/3) sits below any level-3 threshold until
    the matrix alone is ~0.5 GB, so dispatch stays host below the
    threshold — i.e. at realistic sizes — while the call is visible
    everywhere: per-routine counts, call-site profiles and the trace
    all see it.  (Above the threshold a gemv offloads like any other
    call; DFU placement makes a *repeated* huge gemv pay its migration
    once, and the adaptive mode's measured probes will lock host when
    offload loses.)
    """
    m, n = a.shape[-2], a.shape[-1]
    opm, opn = (m, n) if trans == "N" else (n, m)
    dt = a.dtype
    bkey = ("gemv", dt.name, trans)
    compute = _gemv_kernel_t if trans == "T" else _gemv_kernel_n
    # A streams once; x is re-read for every one of the opm output rows.
    ops = [("A", a, 1.0, False), ("X", x, float(opm), False)]
    return _dispatch(routine_name("gemv", dt), opm, opn, 0, ops, compute,
                     key=_call_key(bkey, opm, opn, 0, 1))


def symm(a, b, c=None, *, side="L", uplo="L", alpha=1.0, beta=0.0):
    """C := alpha A B + beta C with A symmetric (one triangle referenced)."""
    return _symm_like(a, b, c, side=side, uplo=uplo, alpha=alpha,
                      beta=beta, conj=False, base="symm")


def hemm(a, b, c=None, *, side="L", uplo="L", alpha=1.0, beta=0.0):
    return _symm_like(a, b, c, side=side, uplo=uplo, alpha=alpha,
                      beta=beta, conj=True, base="hemm")


def _symm_like(a, b, c, *, side, uplo, alpha, beta, conj, base):
    m, n = b.shape[-2], b.shape[-1]
    batch = _batch_of(a, b, c)
    dt = b.dtype
    has_c = c is not None
    av, bv = _hashable(alpha), _hashable(beta)
    bkey = ((base, dt.name, side, uplo, has_c, av, bv)
            if av is not None and bv is not None else None)

    def factory():
        alpha_ = _scalar(alpha, dt)
        beta_ = _scalar(beta, dt)
        if has_c:
            def compute(a_, b_, c_):
                return _symm_kernel(a_, b_, c_, alpha_, beta_, side=side,
                                    uplo=uplo, conj=conj, has_c=True)
        else:
            c0 = _scalar(0.0, dt)

            def compute(a_, b_):
                return _symm_kernel(a_, b_, c0, alpha_, beta_, side=side,
                                    uplo=uplo, conj=conj, has_c=False)
        return compute

    compute = _bound(bkey, factory)
    ops = [("A", a, float(n if side == "L" else m), False),
           ("B", b, float(a.shape[-1]), False)]
    if has_c:
        ops.append(("C", c, 1.0, True))
    shard = (functools.partial(_shard_symm, a, b, c, alpha, beta,
                               side, uplo, conj)
             if _shard_active(batch, a, b, c) else None)
    return _dispatch(routine_name(base, dt), a.shape[-1], n, 0,
                     ops, compute, batch,
                     key=_call_key(bkey, a.shape[-1], n, 0, batch),
                     shard=shard)


def syrk(a, c=None, *, uplo="L", trans="N", alpha=1.0, beta=0.0):
    """C := alpha op(A) op(A)^T + beta C, triangle ``uplo`` only."""
    return _syrk_like(a, c, uplo=uplo, trans=trans, alpha=alpha, beta=beta,
                      conj=False, base="syrk")


def herk(a, c=None, *, uplo="L", trans="N", alpha=1.0, beta=0.0):
    return _syrk_like(a, c, uplo=uplo, trans=trans, alpha=alpha, beta=beta,
                      conj=True, base="herk")


def _syrk_like(a, c, *, uplo, trans, alpha, beta, conj, base):
    n = a.shape[-2] if trans == "N" else a.shape[-1]
    k = a.shape[-1] if trans == "N" else a.shape[-2]
    batch = _batch_of(a, c)
    dt = a.dtype
    has_c = c is not None
    av, bv = _hashable(alpha), _hashable(beta)
    bkey = ((base, dt.name, uplo, trans, has_c, av, bv)
            if av is not None and bv is not None else None)

    def factory():
        alpha_ = _scalar(alpha, dt)
        beta_ = _scalar(beta, dt)
        if has_c:
            def compute(a_, c_):
                return _syrk_kernel(a_, c_, alpha_, beta_, uplo=uplo,
                                    trans=trans, conj=conj, has_c=True)
        else:
            c0 = _scalar(0.0, dt)

            def compute(a_):
                return _syrk_kernel(a_, c0, alpha_, beta_, uplo=uplo,
                                    trans=trans, conj=conj, has_c=False)
        return compute

    def kfactory(block):
        if not has_c and av == 1 and bv == 0:
            def kcompute(a_):              # lean: no scalar epilogue
                return _syrk_klean(a_, uplo=uplo, trans=trans, block=block)
            return kcompute
        alpha_ = _scalar(alpha, dt)
        beta_ = _scalar(beta, dt)
        if has_c:
            def kcompute(a_, c_):
                return _syrk_kvenue(a_, c_, alpha_, beta_, uplo=uplo,
                                    trans=trans, conj=conj, has_c=True,
                                    block=block)
        else:
            c0 = _scalar(0.0, dt)

            def kcompute(a_):
                return _syrk_kvenue(a_, c0, alpha_, beta_, uplo=uplo,
                                    trans=trans, conj=conj, has_c=False,
                                    block=block)
        return kcompute

    def sfactory(scheme, venue, block):
        kern = _split_syrk_kernel(scheme, venue, block)
        alpha_ = _scalar(alpha, dt)
        beta_ = _scalar(beta, dt)
        if has_c:
            def scompute(a_, c_):
                return kern(a_, c_, alpha_, beta_, uplo=uplo,
                            trans=trans, has_c=True)
        else:
            c0 = _scalar(0.0, dt)

            def scompute(a_):
                return kern(a_, c0, alpha_, beta_, uplo=uplo,
                            trans=trans, has_c=False)
        return scompute

    compute = _bound(bkey, factory)
    kernel_compute = _kernel_bound(base, dt, bkey, kfactory)
    flat2d = a.ndim == 2 and (c is None or c.ndim == 2)
    # no sampled-residual check for syrk: the rank-k update has no
    # cancellation channel beyond gemm's and the masked triangle defeats
    # the O(n^2) matvec probe; acceptance rests on the a-priori bound
    split_compute = _split_bound(base, dt, bkey, sfactory, flat2d)
    ops = [("A", a, float(n), False)]
    if has_c:
        ops.append(("C", c, 1.0, True))
    shard = (functools.partial(_shard_syrk, a, c, alpha, beta, uplo,
                               trans, conj)
             if _shard_active(batch, a, c) else None)
    return _dispatch(routine_name(base, dt), n, n, k, ops, compute,
                     batch, key=_call_key(bkey, n, n, k, batch),
                     shard=shard, kernel_compute=kernel_compute,
                     split_compute=split_compute)


def syr2k(a, b, c=None, *, uplo="L", trans="N", alpha=1.0, beta=0.0):
    return _syr2k_like(a, b, c, uplo=uplo, trans=trans, alpha=alpha,
                       beta=beta, conj=False, base="syr2k")


def her2k(a, b, c=None, *, uplo="L", trans="N", alpha=1.0, beta=0.0):
    return _syr2k_like(a, b, c, uplo=uplo, trans=trans, alpha=alpha,
                       beta=beta, conj=True, base="her2k")


def _syr2k_like(a, b, c, *, uplo, trans, alpha, beta, conj, base):
    n = a.shape[-2] if trans == "N" else a.shape[-1]
    k = a.shape[-1] if trans == "N" else a.shape[-2]
    batch = _batch_of(a, b, c)
    dt = a.dtype
    has_c = c is not None
    av, bv = _hashable(alpha), _hashable(beta)
    bkey = ((base, dt.name, uplo, trans, has_c, av, bv)
            if av is not None and bv is not None else None)

    def factory():
        alpha_ = _scalar(alpha, dt)
        beta_ = _scalar(beta, dt)
        if has_c:
            def compute(a_, b_, c_):
                return _syr2k_kernel(a_, b_, c_, alpha_, beta_, uplo=uplo,
                                     trans=trans, conj=conj, has_c=True)
        else:
            c0 = _scalar(0.0, dt)

            def compute(a_, b_):
                return _syr2k_kernel(a_, b_, c0, alpha_, beta_, uplo=uplo,
                                     trans=trans, conj=conj, has_c=False)
        return compute

    compute = _bound(bkey, factory)
    ops = [("A", a, float(n), False), ("B", b, float(n), False)]
    if has_c:
        ops.append(("C", c, 1.0, True))
    shard = (functools.partial(_shard_syr2k, a, b, c, alpha, beta, uplo,
                               trans, conj)
             if _shard_active(batch, a, b, c) else None)
    return _dispatch(routine_name(base, dt), n, n, k, ops, compute,
                     batch, key=_call_key(bkey, n, n, k, batch),
                     shard=shard)


def trmm(a, b, *, side="L", uplo="L", trans="N", diag="N", alpha=1.0):
    """B := alpha op(A) B (or B op(A)), A triangular."""
    return _tri_like(a, b, side=side, uplo=uplo, trans=trans, diag=diag,
                     alpha=alpha, base="trmm", kernel=_trmm_kernel)


def trsm(a, b, *, side="L", uplo="L", trans="N", diag="N", alpha=1.0):
    """Solve op(A) X = alpha B (or X op(A) = alpha B), A triangular."""
    return _tri_like(a, b, side=side, uplo=uplo, trans=trans, diag=diag,
                     alpha=alpha, base="trsm", kernel=_trsm_kernel)


def _tri_like(a, b, *, side, uplo, trans, diag, alpha, base, kernel):
    m, n = b.shape[-2], b.shape[-1]
    batch = _batch_of(a, b)
    dt = b.dtype
    av = _hashable(alpha)
    bkey = ((base, dt.name, side, uplo, trans, diag, av)
            if av is not None else None)

    def factory():
        alpha_ = _scalar(alpha, dt)

        def compute(a_, b_):
            return kernel(a_, b_, alpha_, side=side, uplo=uplo,
                          trans=trans, diag=diag)
        return compute

    def kfactory(block):
        if av == 1:
            def kcompute(a_, b_):          # lean: no alpha scaling
                return _trsm_klean(a_, b_, side=side, uplo=uplo,
                                   trans=trans, diag=diag, block=block)
            return kcompute
        alpha_ = _scalar(alpha, dt)

        def kcompute(a_, b_):
            return _trsm_kvenue(a_, b_, alpha_, side=side, uplo=uplo,
                                trans=trans, diag=diag, block=block)
        return kcompute

    def sfactory(scheme, venue, block):
        kern = _split_trsm_kernel(scheme, venue, block)
        alpha_ = _scalar(alpha, dt)

        def scompute(a_, b_):
            return kern(a_, b_, alpha_, side=side, uplo=uplo,
                        trans=trans, diag=diag)
        return scompute

    compute = _bound(bkey, factory)
    kernel_compute = _kernel_bound(base, dt, bkey, kfactory)
    flat2d = a.ndim == 2 and b.ndim == 2
    split_compute = (_split_bound(base, dt, bkey, sfactory, flat2d)
                     if base == "trsm" else None)
    split_check = None
    if split_compute is not None:
        from repro.core import precision as prc

        def split_check(out, a_, b_):
            tri = _tri_ref(a_, uplo, diag)
            return prc.trsm_residual(out, tri, b_,
                                     left_side=(side == "L"),
                                     lower=(uplo == "L"),
                                     trans_a=(trans != "N"),
                                     alpha=alpha)
    tri_n = a.shape[-1]
    opn = n if side == "L" else m
    ops = [("A", a, float(opn), False),
           ("B", b, float(tri_n), True)]
    shard = (functools.partial(_shard_tri, a, b, side, uplo, trans, diag,
                               alpha, kernel)
             if _shard_active(batch, a, b) else None)
    return _dispatch(routine_name(base, dt), tri_n, opn, 0, ops, compute,
                     batch, key=_call_key(bkey, tri_n, opn, 0, batch),
                     shard=shard, kernel_compute=kernel_compute,
                     split_compute=split_compute,
                     split_check=split_check)


# dlsym mode with no runtime installed still honors the env-derived
# dispatch_cache knob: resolve it once at import through the config
# boundary (runtime construction re-resolves it from its own config).
refresh_cache_flag()
