"""Sharding machinery on a small fake mesh (subprocess: own XLA_FLAGS)."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_debug_mesh
from repro.launch.specs import axis_env_for, build_cell
from repro.models.registry import Model, get_config
from repro.models.sharding import axis_env
from repro.launch import shardings as shd

mesh = make_debug_mesh(8, model=2)
assert mesh.devices.size == 8

# sanitize: drops non-divisible, honors fallback
spec = shd.sanitize(P("model", None), (7, 4), mesh)
assert spec == P(None, None), spec
spec = shd.sanitize(P(None, None, "model", None, None),
                    (2, 2, 3, 8, 16), mesh, fallbacks={2: 4})
assert spec == P(None, None, None, None, "model"), spec

# a reduced arch lowers + compiles on the debug mesh
cfg = get_config("qwen1_5_4b").reduced()
model = Model.from_config(cfg)
with mesh, axis_env(axis_env_for(mesh)):
    cell = build_cell(model, "q", "train_4k", mesh)
    # shrink the batch spec shapes for the debug run
    import repro.launch.specs as S
    jitted = jax.jit(cell.fn, out_shardings=cell.out_shardings,
                     donate_argnums=cell.donate_argnums)
    lowered = jitted.lower(*cell.args)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
print(json.dumps({"ok": True, "flops": float(cost.get("flops", 0))}))
"""


def test_small_mesh_dryrun():
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["ok"] and rec["flops"] > 0
