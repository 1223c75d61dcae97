"""Compile the main path's kernels for a described TPU v5e (no chip).

The TPU compiler is installed beside JAX and compiles for a chip that is
described rather than attached, so these tests refuse here what Mosaic or
XLA would refuse on the chip: unaligned slices, 64-bit indices, VMEM or
HBM overruns.  Nothing runs.  Every compile is at the chip smoke test's
widths.  The topology is described inside a fixture (never at import):
only the worker that runs this file loads the TPU library.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.core import blas, precision
from repro.kernels import ops as kops
from repro.kernels.gemm import gemm
from repro.kernels.syrk import syrk
from repro.kernels.trsm import trsm

N = 4096                                  # chip_smoke.PALLAS_N
V5E_HBM = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep it out."""
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(autouse=True)
def tpu_default_backend(monkeypatch):
    """Pick kernels as a TPU host does: the BLAS entry points then lower
    through the per-platform choice of :mod:`repro.kernels.ops`."""
    monkeypatch.setattr(kops, "_backend", lambda: "tpu")


def _compile(fn, *args):
    with jax.enable_x64(True):      # as chip_smoke.py runs
        return jax.jit(fn).lower(*args).compile()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits(compiled) -> bool:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes) < V5E_HBM


@pytest.mark.parametrize("kernel", ["gemm", "split_pass"])
def test_pallas_gemm_compiles(one_chip, kernel):
    out_dtype = jnp.float32 if kernel == "split_pass" else None
    c = _compile(functools.partial(gemm, out_dtype=out_dtype),
                 _spec(one_chip, (N, N)), _spec(one_chip, (N, N)))
    assert "tpu_custom_call" in c.as_text()


def test_pallas_syrk_compiles(one_chip):
    c = _compile(functools.partial(syrk, uplo="L", trans="N"),
                 _spec(one_chip, (N, N)))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("side,uplo,trans,diag",
                         [("L", "L", "N", "N"), ("R", "U", "T", "U")])
def test_pallas_trsm_compiles(one_chip, side, uplo, trans, diag):
    c = _compile(functools.partial(trsm, side=side, uplo=uplo,
                                   trans=trans, diag=diag),
                 _spec(one_chip, (N, N)), _spec(one_chip, (N, N)))
    assert "tpu_custom_call" in c.as_text()


def test_split_gemm_on_pallas_passes_compiles(one_chip):
    mm = functools.partial(gemm, out_dtype=jnp.float32)
    c = _compile(lambda a, b: precision.matmul(a, b, "split2", mm=mm),
                 _spec(one_chip, (N, N), jnp.float64),
                 _spec(one_chip, (N, N), jnp.float64))
    assert "tpu_custom_call" in c.as_text()


def test_blas_gemm_lowers_per_platform(one_chip):
    """One traced sgemm: lowered for the chip it runs the Pallas kernel;
    lowered for the CPU (the host path's operands on a TPU host) it runs
    the reference, since a TPU kernel cannot be lowered there."""
    f = functools.partial(blas._gemm_kernel, trans_a="N", trans_b="N",
                          has_c=False)
    scalar = jnp.zeros((), jnp.float32)
    args = (jnp.zeros((N, 384), jnp.float32),
            jnp.zeros((384, N), jnp.float32), None, scalar, scalar)
    cpu = jax.jit(f).lower(*args).compile()
    assert "tpu_custom_call" not in cpu.as_text()
    c = _compile(f, *(_spec(one_chip, x.shape) if x is not None else None
                      for x in args))
    assert "tpu_custom_call" in c.as_text()


def test_parsec_dgemm_fits_one_chip(one_chip):
    """PARSEC's fp64 dgemm^T against the 1.8 GB panel: the contraction
    chunking keeps the TPU's fp64 emulation scratch inside 16 GB."""
    f64 = jnp.float64
    c = _compile(functools.partial(blas._gemm_kernel, trans_a="T",
                                   trans_b="N", has_c=False),
                 _spec(one_chip, (93536, 32), f64),
                 _spec(one_chip, (93536, 2400), f64),
                 *(_spec(one_chip, (), f64) for _ in range(3)))
    assert _fits(c)


@pytest.mark.parametrize("op", ["cgemm", "ctrsm"])
def test_lsms_complex64_compiles(one_chip, op):
    """The blocked LU's trailing update and panel-row solve at LSMS's
    n = 6912 in complex64 (the TPU compiler refuses complex128 for
    both)."""
    n, nb, c64 = 6912, 256, jnp.complex64
    if op == "cgemm":
        c = _compile(functools.partial(blas._gemm_kernel, trans_a="N",
                                       trans_b="N", has_c=True),
                     _spec(one_chip, (n - nb, nb), c64),
                     _spec(one_chip, (nb, n - nb), c64),
                     _spec(one_chip, (n - nb, n - nb), c64),
                     _spec(one_chip, (), c64), _spec(one_chip, (), c64))
    else:
        c = _compile(functools.partial(blas._trsm_kernel, side="L",
                                       uplo="L", trans="N", diag="U"),
                     _spec(one_chip, (nb, nb), c64),
                     _spec(one_chip, (nb, n - nb), c64),
                     _spec(one_chip, (), c64))
    assert _fits(c)
