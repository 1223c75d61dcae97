import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count="
                           + os.environ.get("DRYRUN_DEVICES", "512")).strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: for the
16x16 single-pod mesh and the 2x16x16 multi-pod mesh, every cell's step
function must ``.lower().compile()`` under SPMD partitioning, and the
compiled artifact yields ``memory_analysis()`` (fits?) and
``cost_analysis()`` + collective-parse (roofline terms, EXPERIMENTS.md).

The XLA_FLAGS line above MUST run before any other jax-touching import —
device count locks at first backend init.

Usage:
    python -m repro.launch.dryrun --arch qwen2_5_32b --shape train_4k \
        --mesh single
    python -m repro.launch.dryrun --all --mesh both --out experiments/dryrun
"""
import argparse
import json
import time
import traceback
from typing import Optional

import jax
import jax.numpy as jnp

from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (SHAPES, Cell, CellSkipped, axis_env_for,
                                build_cell)
from repro.models import ARCHS
from repro.models.registry import Model, get_config
from repro.models.sharding import axis_env
from repro.roofline.analysis import V5E, collective_bytes, roofline_terms


def model_flops_for(cfg, shape) -> float:
    """MODEL_FLOPS: 6·N·D for training, 2·N_active·D for inference."""
    total, active = cfg.param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq
        return 6.0 * active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq
        return 2.0 * active * tokens
    tokens = shape.global_batch * 1
    return 2.0 * active * tokens


# --------------------------------------------------------------------- #
# scan-aware cost model                                                  #
# --------------------------------------------------------------------- #
# XLA's cost_analysis counts a while-loop (lax.scan) body ONCE, not
# trip_count times, so a 64-layer model scanned over 32 groups reports
# ~1/32 of its real FLOPs. We recover true costs by compiling depth-
# scaled variants at k=1 and k=2 scan groups and fitting
# cost(k) = intercept + slope*k (embed/loss/optimizer live in the
# intercept; per-layer work in the slope), evaluated at the full depth.
def _with_groups(cfg, k: int):
    import dataclasses as _dc
    from repro.models.transformer import period_of
    if cfg.family == "encdec":
        return _dc.replace(cfg, n_layers=k, encoder_layers=k)
    period = period_of(cfg)
    return _dc.replace(cfg, n_layers=k * period)


def _n_groups(cfg) -> int:
    from repro.models.transformer import period_of
    if cfg.family == "encdec":
        return cfg.n_layers
    return cfg.n_layers // period_of(cfg)


def _cost_probe(cfg, shape_name: str, mesh, k: int, **cell_kw) -> dict:
    """Compile the k-group variant UNROLLED (true per-layer costs);
    return per-device cost + collective bytes."""
    sub = _with_groups(cfg, k)
    model = Model.from_config(sub)
    cell = build_cell(model, sub.name, shape_name, mesh, unroll=True,
                      **cell_kw)
    jitted = jax.jit(cell.fn, out_shardings=cell.out_shardings,
                     donate_argnums=cell.donate_argnums)
    compiled = jitted.lower(*cell.args).compile()
    try:
        cost = compiled.cost_analysis() or {}
    except Exception:
        cost = {}
    colls = collective_bytes(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": float(sum(colls.values()))}


def extrapolated_cost(cfg, shape_name: str, mesh, **cell_kw) -> dict:
    k_full = _n_groups(cfg)
    c1 = _cost_probe(cfg, shape_name, mesh, 1, **cell_kw)
    if k_full == 1:
        c1["probe_k1"] = dict(c1)
        return c1
    c2 = _cost_probe(cfg, shape_name, mesh, 2, **cell_kw)
    out = {}
    for key in c1:
        slope = c2[key] - c1[key]
        out[key] = max(0.0, c1[key] + slope * (k_full - 1))
    out["probe_k1"] = c1           # intercept: embed/loss/optimizer
    out["probe_k2"] = c2           # +1 layer group: per-layer slope
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             remat: str = "dots", n_micro: int = 1, zero: bool = False,
             grad_compress: bool = False, moe_impl: str = "scatter",
             overrides: Optional[dict] = None,
             out_dir: Optional[str] = None, verbose: bool = True,
             tag: str = "") -> dict:
    import dataclasses as _dc
    mesh_name = "multi" if multi_pod else "single"
    cfg = get_config(arch)
    if overrides:
        cfg = _dc.replace(cfg, **overrides)
    model = Model.from_config(cfg)
    shape = SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "remat": remat, "n_micro": n_micro, "zero": zero,
           "grad_compress": grad_compress, "moe_impl": moe_impl,
           "overrides": overrides or {}, "tag": tag,
           "status": "?"}
    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        with mesh, axis_env(axis_env_for(mesh)):
            cell = build_cell(model, arch, shape_name, mesh, remat=remat,
                              n_micro=n_micro, zero=zero,
                              grad_compress=grad_compress,
                              moe_impl=moe_impl)
            jitted = jax.jit(cell.fn, out_shardings=cell.out_shardings,
                             donate_argnums=cell.donate_argnums)
            lowered = jitted.lower(*cell.args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

            try:
                mem = compiled.memory_analysis()
                rec["memory"] = {
                    k: int(getattr(mem, k))
                    for k in ("argument_size_in_bytes",
                              "output_size_in_bytes",
                              "temp_size_in_bytes",
                              "generated_code_size_in_bytes")
                    if hasattr(mem, k)}
            except Exception as e:          # CPU backend may lack this
                rec["memory"] = {"error": str(e)}
            try:
                cost = compiled.cost_analysis() or {}
            except Exception:
                cost = None
            if not cost or "flops" not in (cost or {}):
                try:
                    cost = lowered.cost_analysis() or {}
                except Exception:
                    cost = cost or {}
            hlo = compiled.as_text()
            rec["collectives"] = collective_bytes(hlo)
            rec["hlo_bytes"] = len(hlo)
            rec["cost_raw"] = {k: float(v) for k, v in (cost or {}).items()
                               if isinstance(v, (int, float))
                               and k in ("flops", "bytes accessed",
                                         "transcendentals")}
            # scan-aware true per-device cost (see extrapolated_cost)
            fit = extrapolated_cost(
                cfg, shape_name, mesh, remat=remat, n_micro=n_micro,
                zero=zero, grad_compress=grad_compress, moe_impl=moe_impl)
            rec["cost"] = {"flops": fit["flops"],
                           "bytes accessed": fit["bytes"]}
            rec["coll_bytes_fit"] = fit["coll"]
            rec["probes"] = {k: fit[k] for k in ("probe_k1", "probe_k2")
                             if k in fit}
            chips = mesh.devices.size
            rep = roofline_terms(
                arch=arch, shape=shape_name, mesh_name=mesh_name,
                chips=chips, cost=rec["cost"], hlo_text="",
                model_flops=model_flops_for(cfg, shape),
                coll_bytes=fit["coll"])
            rec["roofline"] = rep.row()
            rec["chips"] = chips
            rec["lower_s"] = round(t_lower, 1)
            rec["compile_s"] = round(t_compile, 1)
            rec["status"] = "ok"
    except CellSkipped as e:
        rec["status"] = "skipped"
        rec["reason"] = str(e)
    except Exception as e:
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["wall_s"] = round(time.time() - t0, 1)

    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = f"__{tag}" if tag else ""
        path = os.path.join(out_dir,
                            f"{arch}__{shape_name}__{mesh_name}{suffix}"
                            ".json")
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
    if verbose:
        r = rec.get("roofline", {})
        print(f"[{rec['status']:7s}] {arch:24s} {shape_name:12s} "
              f"{mesh_name:6s} wall={rec['wall_s']:7.1f}s "
              f"dom={r.get('dominant', '-'):10s} "
              f"frac={r.get('roofline_fraction', 0):.3f}"
              + (f"  ERR {rec.get('error', '')[:120]}"
                 if rec["status"] == "error" else ""))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--remat", default="dots",
                    choices=["none", "dots", "full"])
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--zero", action="store_true")
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--moe-impl", default="scatter",
                    choices=["scatter", "dense", "a2a"])
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    archs = ARCHS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) \
        else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    results = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                results.append(run_cell(
                    arch, shape, mp, remat=args.remat,
                    n_micro=args.n_micro, zero=args.zero,
                    grad_compress=args.grad_compress,
                    moe_impl=args.moe_impl, out_dir=args.out))
    n_ok = sum(r["status"] == "ok" for r in results)
    n_skip = sum(r["status"] == "skipped" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\ndry-run: {n_ok} ok, {n_skip} skipped, {n_err} errors "
          f"of {len(results)} cells")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
