"""MuST/LSMS KKR solves, as the application calls them: per energy and
atom, the KKR matrix ``M = I - t G`` over the atom's local interaction
zone (LIZ), ``zgetrf`` and ``zgetrs``, through plain ``jnp.einsum`` and
``jax.scipy.linalg``.

The single-site scattering matrix t is block-diagonal: one kkrsz x kkrsz
block per LIZ atom, the t-matrix of that atom's species at the energy.
The structure constants G (n x n, n = LIZ x kkrsz) depend on the energy
and on the LIZ's geometry, which every atom of the lattice shares: the
host makes G afresh at each energy (a fresh host buffer, first touched
there) and Device First-Use moves it once for all of that energy's
atoms.  One step is one (energy, atom) solve, energies outermost and
atoms innermost as LSMS loops: the build ``t G`` as one batched product
of the t blocks with G's block rows (8 n^2 kkrsz real operations), the
blocked LU of M, and the solve against the central atom's block column
of t (nrhs = kkrsz), a fresh host buffer each step.  The solution tau
is pulled to the host, where LSMS builds the Green's function from it,
and that pull ends the step.

The check holds every step's tau to the KKR system in complex128 on the
host, and one step's factors, drawn from the seed, to the KKR matrix:
sampled columns of ``P L U - M`` against M built in complex128.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Tuple

import numpy as np

from bench import cost, data

SPANS = ("lsms.make_g", "lsms.build", "lsms.lu_factor", "lsms.lu_solve")
#: columns of the kept step's factors compared with the KKR matrix
LU_COLUMNS = 32


def energy(e: int) -> complex:
    """The e-th point of the energy contour, by which G's seeded
    pattern is scaled: |z| from 0.6 to 1.3, so that t G has a spectral
    radius of about 0.3 to 0.65 and M is some way from the identity at
    every energy."""
    return complex(0.6 + 0.02 * e, 0.05 + 0.01 * e)


def build(t_blocks, g_rows):
    """``t G`` for block-diagonal t: each LIZ atom's t block times its
    block row of G, one batched product through the intercepted entry
    point."""
    import jax.numpy as jnp
    return jnp.einsum("iab,ibc->iac", t_blocks, g_rows)


def factor(m):
    import jax.scipy.linalg as jsl
    return jsl.lu_factor(m)


def solve(lu_piv, b):
    import jax.scipy.linalg as jsl
    return jsl.lu_solve(lu_piv, b)


@dataclasses.dataclass
class State:
    cfg: dict
    seed: int
    g0: np.ndarray                  # G's pattern (n x n), host memory
    t_species: np.ndarray           # (energies, species, kkrsz, kkrsz)
    liz_species: np.ndarray         # (atoms, liz): each LIZ atom's species
    session: object = None
    g_energy: int = -1
    g_app: object = None            # G at g_energy, the application's
    steps: int = 0
    taus: List[Tuple[int, int, np.ndarray]] = dataclasses.field(
        default_factory=list)
    kept: Optional[Tuple[int, int, object]] = None
    kept_lu: Optional[Tuple[int, int, np.ndarray, np.ndarray]] = None
    rng: Optional[np.random.Generator] = None
    build: Callable = build
    factor: Callable = factor
    solve: Callable = solve

    def reset(self) -> None:
        self.steps, self.taus, self.kept = 0, [], None
        self.rng = np.random.default_rng(data.seed_words(self.seed) + [3])


def sizes(cfg: dict) -> Tuple[int, int, int]:
    n, kkrsz = cfg["n"], cfg["kkrsz"]
    if n % kkrsz:
        raise ValueError(f"n = {n} is not a whole number of {kkrsz}-blocks")
    return n, kkrsz, n // kkrsz


def make_data(cfg: dict, seed: int):
    n, kkrsz, liz = sizes(cfg)
    dtype = np.dtype(cfg["dtype"])
    words = data.seed_words(seed)
    # complex Gaussian entries of variance radius^2 / n: the spectral
    # radius of G's pattern is about cfg["g_radius"] (the circular law)
    g0 = data.normal(words + [0], (n, n), dtype)
    g0 *= dtype.type(cfg["g_radius"] / np.sqrt(2 * n))
    t = data.normal(words + [1], (cfg["energies"], cfg["species"], kkrsz,
                                  kkrsz), dtype)
    t *= dtype.type(cfg["t_noise"])
    t[..., np.arange(kkrsz), np.arange(kkrsz)] += 1
    rng = np.random.default_rng(words + [2])
    species = rng.integers(0, cfg["species"],
                           size=(cfg["atoms_per_node"], liz))
    return g0, t, species


def t_blocks(st: State, e: int, a: int) -> np.ndarray:
    """The atom's block-diagonal t at energy e: its LIZ atoms' blocks,
    the atom itself first."""
    return st.t_species[e][st.liz_species[a]]


def rhs(st: State, e: int, a: int) -> np.ndarray:
    """The central atom's block column of t: its t block on top of
    zeros."""
    n, kkrsz, _ = sizes(st.cfg)
    b = np.zeros((n, kkrsz), st.g0.dtype)
    b[:kkrsz] = st.t_species[e][st.liz_species[a, 0]]
    return b


def g_of(st: State, e: int) -> np.ndarray:
    """G at energy e, as LIZ block rows (liz, kkrsz, n)."""
    n, kkrsz, liz = sizes(st.cfg)
    g = np.multiply(st.g0, np.asarray(energy(e), st.g0.dtype))
    return g.reshape(liz, kkrsz, n)


def setup(cfg: dict, traffic: dict, seed: int) -> State:
    import repro
    from repro import OffloadConfig
    g0, t, species = make_data(cfg, seed)
    st = State(cfg=cfg, seed=seed, g0=g0, t_species=t,
               liz_species=species)
    st.session = repro.session(OffloadConfig(**cfg["offload"]))
    st.reset()
    # warm-up: one whole solve compiles every program of a step and
    # moves the first energy's G to HBM
    step(st)
    st.reset()
    return st


def step(st: State) -> None:
    import jax
    from repro.apps.lsms import kkr_matrix
    from repro.core import host_array
    n = st.cfg["n"]
    e, a = divmod(st.steps, st.cfg["atoms_per_node"])
    e %= st.cfg["energies"]
    if e != st.g_energy:
        with jax.profiler.TraceAnnotation("lsms.make_g"):
            st.g_app = None
            st.g_app = host_array(g_of(st, e))
            st.g_energy = e
    with jax.profiler.TraceAnnotation("lsms.build"):
        tg = st.build(host_array(t_blocks(st, e, a)), st.g_app)
        m = kkr_matrix(tg.reshape(n, n), 1.0)
    with jax.profiler.TraceAnnotation("lsms.lu_factor"):
        lu_piv = st.factor(m)
    with jax.profiler.TraceAnnotation("lsms.lu_solve"):
        tau = st.solve(lu_piv, host_array(rhs(st, e, a)))
        tau = np.asarray(tau)
    st.taus.append((e, a, tau))
    st.steps += 1
    # one step's factors kept for the check, each step with equal chance
    if st.rng.integers(0, st.steps) == 0:
        st.kept = (e, a, lu_piv)


def close(st: State) -> None:
    """Pull the kept step's factors, then free the session and every
    device array."""
    if st.kept is not None:
        e, a, (lu, piv) = st.kept
        st.kept_lu = (e, a, np.asarray(lu), np.asarray(piv))
    st.kept = None
    if st.session is not None:
        st.session.close()
    st.session = st.g_app = None


def flops_per_step(cfg: dict) -> float:
    n, kkrsz, liz = sizes(cfg)
    dt = cfg["dtype"]
    return (cost.gemm_flops(kkrsz, n, kkrsz, dt, batch=liz)
            + cost.getrf_flops(n, dt) + cost.getrs_flops(n, kkrsz, dt))


def _apply_t(tb: np.ndarray, x: np.ndarray) -> np.ndarray:
    """Block-diagonal t (liz, kkrsz, kkrsz) times x (n, cols)."""
    liz, kkrsz, _ = tb.shape
    return np.einsum("iab,ibc->iac", tb,
                     x.reshape(liz, kkrsz, -1)).reshape(x.shape)


def row_order(piv: np.ndarray) -> np.ndarray:
    """The rows of M in the order of P M = L U, from LAPACK's sequential
    row swaps (0-based)."""
    perm = np.arange(len(piv))
    for i, p in enumerate(piv):
        perm[[i, p]] = perm[[p, i]]
    return perm


def check(st: State) -> Tuple[List[Tuple[str, float, float]], int]:
    """Every step's tau against the KKR system in complex128: the
    residual ``max|tau - t (G tau) - b| / max|b|`` (M is never formed).
    And the kept step's factors: sampled columns, drawn from the seed,
    of ``P L U - M`` against M in complex128, as
    ``max|P M - L U| / max|M|`` over those columns.  Returns the compared
    numbers with their limits, and how many of the answers compared (each
    step's tau, the kept factors) exceed their limit."""
    lim = st.cfg["limits"]
    wide = np.complex128
    g0 = st.g0.astype(wide)
    worst, failed = 0.0, 0
    for e, a, tau in st.taus:
        x = tau.astype(wide)
        b = rhs(st, e, a).astype(wide)
        tb = t_blocks(st, e, a).astype(wide)
        mx = x - _apply_t(tb, energy(e) * (g0 @ x))
        r = float(np.max(np.abs(mx - b)) / np.max(np.abs(b)))
        worst = max(worst, r)
        failed += r > lim["solve_residual"]
    e, a, lu, piv = st.kept_lu
    n = lu.shape[0]
    cols = np.sort(np.random.default_rng(
        data.seed_words(st.seed) + [7]).choice(
            n, size=min(LU_COLUMNS, n), replace=False))
    lower = np.tril(lu, -1).astype(wide)
    lower[np.diag_indices(n)] = 1
    upper = lu[:, cols].astype(wide)
    upper[np.arange(n)[:, None] > cols[None, :]] = 0
    m = -_apply_t(t_blocks(st, e, a).astype(wide),
                  energy(e) * g0[:, cols])
    m[cols, np.arange(len(cols))] += 1
    pm = m[row_order(piv)]
    lu_err = float(np.max(np.abs(pm - lower @ upper)) / np.max(np.abs(m)))
    failed += lu_err > lim["lu_residual"]
    return [("solve_residual", worst, lim["solve_residual"]),
            ("lu_residual", lu_err, lim["lu_residual"])], failed


# --------------------------------------------------------------------- #
# controls: the plain reference put in the program's place, in a lower  #
# precision than the configuration states                                #
# --------------------------------------------------------------------- #
def lu_blocked(m: np.ndarray, nb: int, matmul: Callable
               ) -> Tuple[np.ndarray, np.ndarray]:
    """A plain right-looking blocked LU with partial pivoting, as
    LAPACK's getrf: panels by LAPACK's getrf on the host, U12 by a
    triangular solve, the trailing update ``A22 -= L21 U12`` by
    ``matmul``.  Returns the factors and the 0-based row swaps."""
    import scipy.linalg
    a = np.array(m)
    n = a.shape[0]
    piv = np.arange(n)
    for j in range(0, n, nb):
        w = min(nb, n - j)
        panel, p = scipy.linalg.lu_factor(a[j:, j:j + w],
                                          check_finite=False)
        a[j:, j:j + w] = panel
        for i, q in enumerate(p):
            if q != i:
                rows = [j + i, j + q]
                a[rows, :j] = a[rows[::-1], :j]
                a[rows, j + w:] = a[rows[::-1], j + w:]
        piv[j:j + w] = p + j
        if j + w < n:
            a[j:j + w, j + w:] = scipy.linalg.solve_triangular(
                a[j:j + w, j:j + w], a[j:j + w, j + w:], lower=True,
                unit_diagonal=True, check_finite=False)
            a[j + w:, j + w:] -= matmul(a[j + w:, j:j + w],
                                        a[j:j + w, j + w:])
    return a, piv


def control(st: State, name: str) -> dict:
    """The step computed by the plain reference, off the intercepted
    path, with every product at ``name`` precision ("bf16": one bf16
    pass; "f32": full complex64): the build on the default device, the
    blocked LU with its trailing updates on the default device and its
    panels by LAPACK on the host, the solve by LAPACK in complex64."""
    import jax
    import scipy.linalg
    bmm = jax.jit({"bf16": data.batched_matmul_bf16,
                   "f32": data.batched_matmul_f32}[name])
    mm = jax.jit({"bf16": data.matmul_bf16, "f32": data.matmul_f32}[name])
    nb = st.cfg["nb"]
    return {
        "build": lambda t, g: bmm(np.asarray(t), np.asarray(g)),
        "factor": lambda m: lu_blocked(
            np.asarray(m), nb, lambda x, y: np.asarray(mm(x, y))),
        "solve": lambda lu_piv, b: scipy.linalg.lu_solve(
            lu_piv, np.asarray(b), check_finite=False)}
