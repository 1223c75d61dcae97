"""PARSEC's Chebyshev-filtered subspace iteration, as the application
calls it: S_blk = Hpsi_blk^T Psi through plain ``jnp.einsum``.

The wavefunction panel Psi (ngrid x nstates, fp64) is first touched on
the host, as Fortran allocates it; Device First-Use moves it to HBM at
the first product (set-up) and it stays there.  Each step takes one
wavefront block (ngrid x nblock): a block is used ``reuse`` times, then
the next of the nstates / nblock blocks, cycling.  Every new use gets a
fresh host buffer, standing for the CPU filter rewriting the block.  The
32 x 2400 result is pulled to the host, where PARSEC's Rayleigh-Ritz
runs; the pull ends the step.

The check compares a sample of the window's products, drawn from the
seed, with the product in fp64 on the host.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Tuple

import numpy as np

from bench import cost, data

SPANS = ("parsec.make_block", "parsec.product", "parsec.pull")
#: products kept for the check: a reservoir drawn from the seed
KEEP = 256
FP64_U = 2.0 ** -53


def product(blk, psi):
    """The application's product, through the intercepted entry point."""
    import jax.numpy as jnp
    return jnp.einsum("gi,gj->ij", blk, psi)


@dataclasses.dataclass
class State:
    cfg: dict
    reuse: int
    seed: int
    panel: np.ndarray               # Psi, host memory
    pool: np.ndarray                # (blocks, ngrid, nblock)
    session: object = None
    psi: object = None              # Psi as the application's array
    blk: object = None
    blk_id: int = 0
    uses: int = 0
    steps: int = 0
    kept: List[Tuple[int, np.ndarray]] = dataclasses.field(
        default_factory=list)
    rng: Optional[np.random.Generator] = None
    product: Callable = product

    def reset(self) -> None:
        """Start the block cycle and the kept sample afresh."""
        self.blk, self.blk_id, self.uses, self.steps = None, 0, 0, 0
        self.kept = []
        self.rng = np.random.default_rng(data.seed_words(self.seed) + [1])


def make_data(cfg: dict, seed: int) -> Tuple[np.ndarray, np.ndarray]:
    ngrid, nstates, nblock = cfg["ngrid"], cfg["nstates"], cfg["nblock"]
    words = data.seed_words(seed)
    panel = data.normal(words + [0], (ngrid, nstates), cfg["dtype"])
    pool = data.normal(words + [2], (nstates // nblock, ngrid, nblock),
                       cfg["dtype"])
    return panel, pool


def setup(cfg: dict, traffic: dict, seed: int) -> State:
    import repro
    from repro import OffloadConfig
    from repro.core import host_array
    panel, pool = make_data(cfg, seed)
    st = State(cfg=cfg, reuse=int(traffic["reuse"]), seed=seed,
               panel=panel, pool=pool)
    st.session = repro.session(OffloadConfig(**cfg["offload"]))
    st.psi = host_array(panel)
    st.reset()
    # warm-up: the panel's first use moves it to HBM, and the second step
    # takes the window's placement path for the block (a hit, or a fresh
    # block when reuse is 1)
    for _ in range(2):
        step(st)
    st.reset()
    return st


def step(st: State) -> None:
    import jax
    from repro.core import host_array
    if st.uses == 0:
        with jax.profiler.TraceAnnotation("parsec.make_block"):
            st.blk = host_array(st.pool[st.blk_id])
    with jax.profiler.TraceAnnotation("parsec.product"):
        s = st.product(st.blk, st.psi)
    with jax.profiler.TraceAnnotation("parsec.pull"):
        s = np.asarray(s)
    _keep(st, (st.blk_id, s))
    st.steps += 1
    st.uses += 1
    if st.uses == st.reuse:
        st.uses = 0
        st.blk_id = (st.blk_id + 1) % len(st.pool)


def _keep(st: State, item) -> None:
    """Reservoir sampling: every product is kept with equal chance."""
    if len(st.kept) < KEEP:
        st.kept.append(item)
        return
    j = int(st.rng.integers(0, st.steps + 1))
    if j < KEEP:
        st.kept[j] = item


def close(st: State) -> None:
    if st.session is not None:
        st.session.close()
    st.session = st.psi = st.blk = None


def flops_per_step(cfg: dict) -> float:
    return cost.gemm_flops(cfg["nblock"], cfg["nstates"], cfg["ngrid"],
                           cfg["dtype"])


def check(st: State) -> Tuple[List[Tuple[str, float, float]], int]:
    """Each kept product against fp64 on the host, as the largest
    ``|S - S_ref| / (2 K u |Hpsi|^T |Psi|)`` (a length-K dot's
    worst-case rounding bound in fp64, taken twice: the result and the
    reference).  Returns the compared numbers with their limits, and how
    many kept products exceed the limit."""
    limit = st.cfg["limits"]["err_over_fp64_bound"]
    ids = sorted({i for i, _ in st.kept})
    nb = st.cfg["nblock"]
    blocks = np.concatenate([st.pool[i] for i in ids], axis=1)
    ref = blocks.T @ st.panel
    scale = np.abs(blocks).T @ np.abs(st.panel)
    bound = 2.0 * st.cfg["ngrid"] * FP64_U * scale
    col = {i: c * nb for c, i in enumerate(ids)}
    errs = []
    for i, s in st.kept:
        r = slice(col[i], col[i] + nb)
        errs.append(float(np.max(np.abs(s - ref[r]) / bound[r])))
    worst = max(errs)
    failed = sum(e > limit for e in errs)
    return [("err_over_fp64_bound", worst, limit)], failed


# --------------------------------------------------------------------- #
# controls: the plain reference put in the program's place, in a lower  #
# precision than the configuration states                                #
# --------------------------------------------------------------------- #
def control(st: State, name: str) -> dict:
    """The product computed by the plain reference at ``name``
    precision ("f32" or "bf16") on the default device, in the place of
    the application's product."""
    import jax
    import jax.numpy as jnp
    mm = {"f32": data.matmul_f32, "bf16": data.matmul_bf16}[name]
    psi = jax.device_put(st.panel.astype(np.float32))

    @jax.jit
    def prod(blk, psi):
        return mm(blk.T, psi)

    return {"product": lambda blk, _psi: prod(jnp.asarray(blk, jnp.float32),
                                              psi)}
