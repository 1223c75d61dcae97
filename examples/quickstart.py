"""Quickstart: automatic BLAS offload on unmodified JAX code.

    PYTHONPATH=src python examples/quickstart.py

Mirrors the paper's usage model as a first-class session: inside
`repro.session(config)`, plain jnp.matmul/jnp.dot/jnp.einsum calls are
intercepted, placed per the Device First-Use policy, and counted.  The
config is a typed `OffloadConfig` — env `SCILIB_*` vars still layer in
through `OffloadConfig.from_env()` (so `SCILIB_DEVICES=4` exercises the
multi-device tile scheduler on any backend), and the legacy
`scilib.install()/uninstall()` surface remains as a shim.
"""
import numpy as np
import jax.numpy as jnp

import repro
import repro.core as scilib
from repro import OffloadConfig


def application_code(a, b):
    """Completely ordinary JAX code — no scilib imports, no changes."""
    c = jnp.matmul(a, b)                 # offloaded (large)
    for _ in range(5):
        c = jnp.matmul(a, c)             # reuses device-resident a, c
    d = jnp.einsum("ij,kj->ik", c, b)    # transposed gemm, intercepted
    small = jnp.dot(a[:64, :64], b[:64, :64])   # stays on host (N_avg)
    y = jnp.matmul(a, b[:, 0])           # gemv-shaped: counted, host
    return c, d, small, y


def main():
    rng = np.random.default_rng(0)
    # host_array = the malloc() analogue: inputs are CPU-first-touched
    a = scilib.host_array(rng.standard_normal((768, 768)).astype("float32"))
    b = scilib.host_array(rng.standard_normal((768, 768)).astype("float32"))

    # the script's defaults, with env knobs (SCILIB_THRESHOLD=10,
    # SCILIB_DEVICES=4, ...) layering over them — same precedence as
    # the legacy install(policy="dfu", threshold=500) this replaces
    config = OffloadConfig.legacy(policy="dfu", threshold=500.0)
    with repro.session(config) as s:
        c, d, small, y = application_code(a, b)
        print(s.report())
        reuse = s.runtime.mean_buffer_reuse()
    print(f"\nresult tier: {scilib.memspace.tier_of(c)} "
          f"({scilib.memspace.active().describe()})")
    print(f"mean buffer reuse: {reuse:.1f}")
    # verify against plain execution (the session is closed: these run
    # through the original, un-intercepted symbols)
    c2, d2, small2, y2 = application_code(a, b)
    np.testing.assert_allclose(c, c2, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(d, d2, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(y, y2, rtol=2e-3, atol=2e-3)
    print("results identical with offload enabled: OK")


if __name__ == "__main__":
    from repro import compile_cache
    compile_cache.enable()
    main()
