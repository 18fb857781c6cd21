import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (arch × shape) on the production
meshes and record memory / cost / collective analysis for §Roofline.

MUST be run as its own process (the two lines above lock the fake device
count before jax initializes):

    PYTHONPATH=src python -m repro.launch.dryrun \
        [--arch A]... [--shape S]... [--mesh single|multi|both] \
        [--out experiments/dryrun] [--devices 512]

Success criterion (deliverable e): ``.lower().compile()`` succeeds for every
cell on BOTH the (16,16) single-pod and (2,16,16) multi-pod mesh; the printed
``memory_analysis()`` proves per-device fit, ``cost_analysis()`` feeds the
roofline.  Skipped cells (long_500k × full-attention archs) are recorded with
their reason.
"""

import argparse
import json
import time
import traceback


def _mesh_for(name: str, devices_per_pod: int = 256):
    import jax
    import numpy as np

    if name == "multi":
        n = devices_per_pod * 2
        devs = jax.devices()[:n]
        shape = (2, devices_per_pod // 16, 16)
        return jax.make_mesh(shape, ("pod", "data", "model"),
                             devices=devs)
    devs = jax.devices()[:devices_per_pod]
    return jax.make_mesh((devices_per_pod // 16, 16), ("data", "model"),
                         devices=devs)


def run_cell(arch_name: str, shape_name: str, mesh_name: str,
             devices_per_pod: int = 256, smoke: bool = False) -> dict:
    import jax

    from repro.analysis import hlo as hlo_lib
    from repro.launch.api import get_arch

    arch = get_arch(arch_name)
    shape = arch.shapes[shape_name]
    rec = {
        "arch": arch_name, "shape": shape_name, "mesh": mesh_name,
        "family": arch.family, "status": "ok",
    }
    if shape.skip_reason:
        rec["status"] = "skipped"
        rec["skip_reason"] = shape.skip_reason
        return rec
    mesh = _mesh_for(mesh_name, devices_per_pod)
    rec["mesh_shape"] = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_chips = int(mesh.devices.size)
    rec["n_chips"] = n_chips
    cfg = arch.make_config(smoke)
    t0 = time.time()
    try:
        with mesh:
            bundle = arch.make_step(cfg, shape, mesh)
            jitted = jax.jit(
                bundle.step_fn,
                in_shardings=bundle.in_shardings,
                out_shardings=bundle.out_shardings,
                donate_argnums=bundle.donate_argnums,
            )
            lowered = jitted.lower(*bundle.arg_specs)
            rec["lower_s"] = round(time.time() - t0, 2)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 2)
            mem = compiled.memory_analysis()
            print(f"[{arch_name} × {shape_name} × {mesh_name}] "
                  f"memory_analysis: {mem}")
            cost = compiled.cost_analysis()
            print(f"[{arch_name} × {shape_name} × {mesh_name}] "
                  f"cost_analysis: flops={cost.get('flops', 0):.3e} "
                  f"bytes={cost.get('bytes accessed', 0):.3e}")
            rec["memory"] = {
                "argument_bytes": int(mem.argument_size_in_bytes),
                "output_bytes": int(mem.output_size_in_bytes),
                "temp_bytes": int(mem.temp_size_in_bytes),
                "alias_bytes": int(mem.alias_size_in_bytes),
                "peak_bytes": int(getattr(mem, "peak_memory_in_bytes", 0)),
            }
            rec["cost"] = {k: float(v) for k, v in cost.items()
                           if isinstance(v, (int, float))}
            text = compiled.as_text()
            rec["collectives"] = hlo_lib.collective_bytes(text)
            rec["hlo_chars"] = len(text)
    except Exception as e:  # noqa: BLE001 — a failed cell is a bug report
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["total_s"] = round(time.time() - t0, 2)
    return rec


def run_scan_probe(arch_name: str, shape_name: str, mesh_name: str,
                   devices_per_pod: int = 256) -> dict:
    """Separate scan-body cost from prologue/epilogue cost.

    ``cost_analysis()`` counts a while-loop body ONCE regardless of trip
    count (verified empirically: C(L=1) == C(L=2) == C(L=full) for scanned
    models), so per-layer cost must be measured from an *unrolled* module:
    compile n_layers=1 and 2 with ``unroll_layers=True`` — then
      body = C_u(2) − C_u(1)
    is one layer's true cost and the corrected full-model total is
      C_full_reported + (L − 1)·body      (see analysis/roofline.py).
    """
    import dataclasses as _dc

    import jax

    from repro.analysis import hlo as hlo_lib
    from repro.launch.api import get_arch

    arch = get_arch(arch_name)
    shape = arch.shapes[shape_name]
    rec = {"arch": arch_name, "shape": shape_name, "mesh": mesh_name,
           "probe": True, "status": "ok"}
    if shape.skip_reason:
        rec["status"] = "skipped"
        return rec
    mesh = _mesh_for(mesh_name, devices_per_pod)
    base_cfg = arch.make_config(False)
    layer_field = ("n_layers" if hasattr(base_cfg, "n_layers") else
                   "n_blocks" if hasattr(base_cfg, "n_blocks") else None)
    if layer_field is None:
        rec["status"] = "no_scan"
        return rec
    rec["trips"] = getattr(base_cfg, layer_field)
    try:
        costs = {}
        for nl in (1, 2):
            cfg = _dc.replace(base_cfg, **{layer_field: nl,
                                           "unroll_layers": True})
            with mesh:
                bundle = arch.make_step(cfg, shape, mesh)
                compiled = jax.jit(
                    bundle.step_fn, in_shardings=bundle.in_shardings,
                    out_shardings=bundle.out_shardings,
                    donate_argnums=bundle.donate_argnums,
                ).lower(*bundle.arg_specs).compile()
                cost = compiled.cost_analysis()
                coll = hlo_lib.collective_bytes(compiled.as_text())
                costs[nl] = {
                    "flops": float(cost.get("flops", 0.0)),
                    "bytes": float(cost.get("bytes accessed", 0.0)),
                    "collective": float(coll["total"]),
                }
        rec["body"] = {k: costs[2][k] - costs[1][k] for k in costs[1]}
        rec["rest"] = {k: costs[1][k] - rec["body"][k] for k in costs[1]}
    except Exception as e:  # noqa: BLE001
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
    return rec


def main(argv=None) -> int:
    from repro.launch.api import get_arch, list_archs

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", action="append", default=None)
    ap.add_argument("--shape", action="append", default=None)
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="both")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--devices-per-pod", type=int, default=256)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced configs (CI mini dry-run)")
    ap.add_argument("--probe-scan", action="store_true",
                    help="L=1/L=2 scan-body cost probe (see roofline.py)")
    args = ap.parse_args(argv)

    archs = args.arch or list_archs()
    meshes = ("single", "multi") if args.mesh == "both" else (args.mesh,)
    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch_name in archs:
        arch = get_arch(arch_name)
        shapes = args.shape or list(arch.shapes)
        for shape_name in shapes:
            if shape_name not in arch.shapes:
                continue
            for mesh_name in meshes:
                tag = f"{arch_name}__{shape_name}__{mesh_name}"
                if args.probe_scan:
                    tag += "__probe"
                path = os.path.join(args.out, tag + ".json")
                if args.probe_scan:
                    rec = run_scan_probe(arch_name, shape_name, mesh_name,
                                         args.devices_per_pod)
                else:
                    rec = run_cell(arch_name, shape_name, mesh_name,
                                   args.devices_per_pod, args.smoke)
                with open(path, "w") as fh:
                    json.dump(rec, fh, indent=1)
                status = rec["status"]
                extra = (f" ({rec.get('total_s', 0):.0f}s)"
                         if status == "ok" else
                         f" — {rec.get('skip_reason', rec.get('error', ''))}")
                print(f"{tag}: {status}{extra}", flush=True)
                failures += status == "error"
    print(f"dry-run complete; {failures} failures")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
