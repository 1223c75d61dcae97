"""Production mesh construction.

Single pod: 256 chips as (data=16, model=16). Multi-pod: 2 pods x 256
chips as (pod=2, data=16, model=16) — the ``pod`` axis composes with
``data`` for batch sharding and carries the (slower, compressible)
inter-pod gradient reduction. Defined as functions so importing this
module never touches jax device state (the dry-run must set XLA_FLAGS
before first jax init).
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    """A mesh whose axes are all ``Auto``: the model stack places
    activations with ``with_sharding_constraint`` and lets the compiler
    resolve gathers, which explicit axes (``jax.make_mesh``'s default)
    refuse."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_debug_mesh(n_devices: int = 8, model: int = 2):
    """Small mesh over however many (fake) devices a test session has."""
    data = n_devices // model
    return _auto_mesh((data, model), ("data", "model"))


def batch_axes(mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


# --------------------------------------------------------------------- #
# BLAS-offload device set (the multi-device tile scheduler's view)       #
# --------------------------------------------------------------------- #
def offload_devices():
    """Real devices backing the offload runtime's logical device tiers.

    The runtime enumerates N device tiers (``SCILIB_DEVICES`` or
    ``len(jax.devices())``, see ``repro.core.memspace``); tier *i* maps to
    real device ``i % len(jax.devices())`` — with more tiers than
    hardware (``SCILIB_DEVICES`` above the device count) tiers wrap onto the
    same physical device, exactly like :func:`memspace.put_block`.
    """
    from repro.core import memspace
    devs = jax.devices()
    return [devs[i % len(devs)] for i in range(memspace.active().n_devices)]


def make_offload_mesh():
    """1-D ``('blas',)`` mesh over the sharded-dispatch device set, for
    model code that wants its collectives co-located with the BLAS tiles
    the offload runtime schedules."""
    import numpy as np
    seen, unique = set(), []
    for d in offload_devices():
        if d.id not in seen:
            seen.add(d.id)
            unique.append(d)
    return jax.sharding.Mesh(np.array(unique), ("blas",))
