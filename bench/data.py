"""Inputs made from the seed, and the lower-precision products the
controls use.  Nothing here imports the system under test."""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: pieces and threads of a seeded fill: numpy's generators release the
#: interpreter lock while they fill, one generator per piece
_PIECES = 32
_THREADS = 8


def normal(seed, shape, dtype=np.float64) -> np.ndarray:
    """Standard normal entries made from ``seed`` (a sequence of ints
    names an independent stream).  Complex dtypes get independent real
    and imaginary parts.  The same seed gives the same array whatever
    the machine."""
    dtype = np.dtype(dtype)
    real = np.dtype(dtype.char.lower()) if dtype.kind == "c" else dtype
    out = np.empty(shape, dtype)
    flat = out.reshape(-1).view(real)
    gens = [np.random.default_rng(s)
            for s in np.random.SeedSequence(seed).spawn(_PIECES)]
    parts = np.array_split(flat, _PIECES)
    with ThreadPoolExecutor(_THREADS) as pool:
        list(pool.map(lambda gp: gp[0].standard_normal(dtype=real,
                                                       out=gp[1]),
                      zip(gens, parts)))
    return out


def seed_words(seed: int) -> list:
    """A whole number of any size as the words a SeedSequence takes."""
    seed = int(seed) % (1 << 64)
    return [seed & 0xFFFFFFFF, seed >> 32]


def round_bf16(x):
    """``x`` with every real part rounded to bfloat16, in x's dtype."""
    import jax
    import jax.numpy as jnp
    if jnp.iscomplexobj(x):
        return jax.lax.complex(round_bf16(jnp.real(x)),
                               round_bf16(jnp.imag(x))).astype(x.dtype)
    return x.astype(jnp.bfloat16).astype(x.dtype)


def _dot(a, b):
    """``a @ b`` at full f32 precision through ``lax.dot_general``,
    which the interception layer never patches."""
    import jax
    return jax.lax.dot_general(a, b, (((a.ndim - 1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST)


def _bdot(a, b):
    """The batched ``a[i] @ b[i]`` at full f32 precision."""
    import jax
    return jax.lax.dot_general(a, b, (((2,), (1,)), ((0,), (0,))),
                               precision=jax.lax.Precision.HIGHEST)


def _narrow(x):
    import jax.numpy as jnp
    return x.astype(jnp.complex64 if jnp.iscomplexobj(x) else jnp.float32)


def matmul_bf16(a, b):
    """One bf16 pass: inputs rounded to bfloat16, products summed in
    float32 (a TPU's default precision for a float32 matmul)."""
    return _dot(round_bf16(_narrow(a)), round_bf16(_narrow(b)))


def matmul_f32(a, b):
    """float32 (complex64) inputs and accumulation at full f32 precision."""
    return _dot(_narrow(a), _narrow(b))


def batched_matmul_bf16(a, b):
    """``matmul_bf16`` of each pair ``a[i], b[i]``."""
    return _bdot(round_bf16(_narrow(a)), round_bf16(_narrow(b)))


def batched_matmul_f32(a, b):
    """``matmul_f32`` of each pair ``a[i], b[i]``."""
    return _bdot(_narrow(a), _narrow(b))
