"""MuST / LSMS proxy (paper §4.2).

LSMS solves the Kohn-Sham equation via multiple-scattering Green's
functions: per atom, per energy-grid point, per SCF iteration, build the
KKR matrix ``M = I - t·G`` over the local interaction zone and solve
``M tau = t`` — in production via zgetrf/zgetrs, whose panel updates are
the zgemm/ztrsm stream that is 80 %+ of runtime.

``run_mini`` executes the real numerics at laptop scale through the
public ``jax.scipy.linalg`` solve symbols — under ``SCILIB_LAPACK=1``
these are the intercepted solver tier (:mod:`repro.solvers`), so the
runtime sees a genuine LAPACK-shaped BLAS stream wrapped in solver
spans; without it they are the native path. ``production_trace`` emits the 50-node-scale
call structure of Table 3 — one resident KKR buffer per atom reused
across all (energy x SCF) solves, which is precisely the reuse pattern
(~780x) Device First-Use exploits.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import numpy as np

from repro.core.trace import Trace

# Production workload (paper): 5600 atoms over 50 nodes, 32 energies,
# 3 SCF steps. KKR matrix n ~ LIZ x (lmax+1)^2 x spin; n=6912 calibrated
# so the CPU-policy replay reproduces Table 3's 2080 s of zgemm+ztrsm on
# the Grace-Grace node. nb=256 is the production LU blocking (also sets
# Mem-Copy's per-call staging volume, paper: 291.7 s).
PROD = dict(atoms_per_node=112, energies=32, scf=3, n=6912, nb=256,
            nrhs=32)


@dataclasses.dataclass
class LsmsResult:
    energy: float
    n_solves: int
    trace: Trace


def _getrf_stream(t: Trace, tau: int, tmat: int, n: int, nb: int,
                  nrhs: int) -> None:
    """BLAS stream of one blocked zgetrf + zgetrs on buffer ``tau``.

    Fortran LU factors in place: every panel/trailing-matrix call reads
    and writes regions of the SAME allocation — so all calls reference
    one buffer id, exactly what the DBI interceptor observes.
    """
    for j0 in range(0, n - nb, nb):
        rem = n - j0 - nb
        # panel factor stays on the CPU (getf2 is not level-3 BLAS)
        t.panel("z", n - j0, nb, tau)
        # U12 = L11^{-1} A12
        t.trsm("z", nb, rem, tau, tau)
        # A22 -= L21 @ U12   (the hot zgemm)
        t.gemm("z", rem, rem, nb, tau, tau, tau)
    # zgetrs: forward + back substitution against the t-matrix RHS
    t.trsm("z", n, nrhs, tau, tmat)
    t.trsm("z", n, nrhs, tau, tmat)


def production_trace(atoms_per_node: int = PROD["atoms_per_node"],
                     energies: int = PROD["energies"],
                     scf: int = PROD["scf"], n: int = PROD["n"],
                     nb: int = PROD["nb"],
                     nrhs: int = PROD["nrhs"]) -> Trace:
    """One Grace-Hopper node's BLAS stream for the Table 3 workload."""
    t = Trace()
    el = 16  # complex128
    taus = [t.new_buffer(n * n * el, f"tau_atom{a}")
            for a in range(atoms_per_node)]
    tmats = [t.new_buffer(n * nrhs * el, f"t_atom{a}")
             for a in range(atoms_per_node)]
    for _ in range(scf):
        for _e in range(energies):
            for a in range(atoms_per_node):
                _getrf_stream(t, taus[a], tmats[a], n, nb, nrhs)
    return t


# ----------------------------------------------------------------------- #
# runnable mini-app (real numerics through the interception layer)         #
# ----------------------------------------------------------------------- #
def kkr_matrix(tg, z: complex):
    """The KKR matrix ``M = I - z t G`` in ``tg``'s dtype.

    The energy enters as a scalar of that dtype: under x64 a Python
    complex would reach the eager multiply as a complex128 operand, which
    a TPU's compiler cannot lower (it aborts the process).
    """
    import jax.numpy as jnp
    return jnp.eye(tg.shape[0], dtype=tg.dtype) - np.asarray(z, tg.dtype) * tg


def run_mini(atoms: int = 4, energies: int = 4, scf: int = 2,
             n: int = 192, nb: int = 64, seed: int = 0,
             dtype="complex128") -> Dict[str, float]:
    """Tiny LSMS: real KKR-style solves with verification.

    Returns the total energy proxy, the largest residual and the largest
    LAPACK-style scaled residual (``max_ratio``) so tests can assert the
    physics loop is numerically sound under every offload policy.
    ``nb`` is kept for callers, but when the solver tier is patched the
    blocked LU takes its block size from the session's ``lapack_nb``
    (``SCILIB_LAPACK_NB``); the native path ignores it entirely.
    """
    import jax
    import jax.numpy as jnp
    import jax.scipy.linalg as jsl

    from repro.core.policy import host_array

    rng = np.random.default_rng(seed)
    # structure "constants" G per atom: fixed across SCF; host-first-
    # touched like Fortran allocations, reused across all solves
    gmats = [host_array(np.asarray(
        (rng.standard_normal((n, n)) + 1j * rng.standard_normal((n, n)))
        / (2 * n), dtype)) for _ in range(atoms)]
    eps = float(np.finfo(dtype).eps)
    energy = 0.0
    max_resid = 0.0
    max_ratio = 0.0
    n_solves = 0
    tmat_scale = 1.0
    for it in range(scf):
        for e in range(energies):
            z = 0.1 + 0.05 * e + 0.02j
            for a in range(atoms):
                tm = jnp.asarray(
                    tmat_scale * (np.eye(n)
                                  + 0.01 * rng.standard_normal((n, n))),
                    dtype)
                tg = jnp.matmul(tm, gmats[a])    # intercepted zgemm
                # the KKR build stays in the intercepted stream (no
                # host round-trip), and the solve goes through the
                # public scipy symbols: with SCILIB_LAPACK=1 these are
                # the patched solver tier, without it the native path
                m = kkr_matrix(tg, z)
                lu_piv = jsl.lu_factor(m)
                tau = jsl.lu_solve(lu_piv, tm)
                # verification on the host side (numpy): not BLAS stream
                mh, th = np.asarray(m), np.asarray(tau)
                r = mh @ th - np.asarray(tm)
                max_resid = max(max_resid, float(np.max(np.abs(r))))
                # LAPACK's xGET02 test ratio, ||M tau - T||_1 /
                # (||M||_1 ||tau||_1 n eps): O(1) for a backward-stable
                # solve at this dtype's precision
                max_ratio = max(max_ratio, float(
                    np.linalg.norm(r, 1) / (np.linalg.norm(mh, 1)
                                            * np.linalg.norm(th, 1)
                                            * n * eps)))
                energy += float(np.real(np.trace(th))) / n
                n_solves += 1
        tmat_scale *= 0.98  # SCF mixing proxy
    return {"energy": energy, "max_resid": max_resid,
            "max_ratio": max_ratio, "n_solves": n_solves}
