import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST stay first — jax locks the device count at first
init, and the production meshes need 512 placeholder host devices.

Per cell this script:
  1. builds the production mesh (16×16 single-pod / 2×16×16 multi-pod),
  2. builds the cell's step function + ShapeDtypeStruct inputs + shardings
     (src/repro/configs — no real allocation anywhere),
  3. ``jax.jit(fn, in_shardings=...).lower(*specs).compile()``,
  4. records memory_analysis / cost_analysis / parsed collective bytes and
     the three roofline terms to a JSONL artifact.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch internlm2-20b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh single --out results/dryrun.jsonl
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multi  --out results/dryrun_multi.jsonl
"""

import argparse
import json
import time
import traceback


def run_cell(arch_name: str, shape: str, multi_pod: bool, *, policy_overrides=None) -> dict:
    import jax

    from repro.configs.base import get_arch, policy_for_mesh
    from repro.launch.hlo_analysis import analyze_compiled
    from repro.distributed.mesh_utils import make_production_mesh

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_devices = 1
    for s in mesh.shape.values():
        n_devices *= s
    policy = policy_for_mesh(mesh, **(policy_overrides or {}))
    arch = get_arch(arch_name)
    cell = arch.cells()[shape]

    t0 = time.time()
    built = cell.build(mesh, policy)
    with mesh:  # PartitionSpec-based with_sharding_constraints need context
        jit_kwargs = {}
        if built.out_shardings is not None:
            jit_kwargs["out_shardings"] = built.out_shardings
        jitted = jax.jit(built.fn, in_shardings=built.in_shardings, **jit_kwargs)
        lowered = jitted.lower(*built.input_specs)
        t_lower = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t1

    # scan-body correction: XLA counts while-loop bodies once; add
    # (trip_count - 1) x standalone-body cost (see configs.base.ScanCorrection)
    corr_flops = corr_bytes = corr_coll = 0.0
    with mesh:
        for sc in built.scan_corrections:
            body_compiled = (
                jax.jit(sc.fn, in_shardings=sc.in_shardings).lower(*sc.input_specs).compile()
            )
            c = body_compiled.cost_analysis()
            if isinstance(c, list):
                c = c[0]
            from repro.launch.hlo_analysis import collective_bytes_from_hlo

            coll = collective_bytes_from_hlo(body_compiled.as_text())
            corr_flops += sc.multiplier * float(c.get("flops", 0.0))
            corr_bytes += sc.multiplier * float(c.get("bytes accessed", 0.0))
            corr_coll += sc.multiplier * float(coll["total"])

    terms, extra = analyze_compiled(
        compiled,
        n_devices,
        built.model_flops_per_step,
        extra_flops=corr_flops,
        extra_bytes=corr_bytes,
        extra_collective=corr_coll,
    )
    record = {
        "arch": arch_name,
        "shape": shape,
        "kind": cell.kind,
        "mesh": "multi" if multi_pod else "single",
        "n_devices": n_devices,
        "description": built.description,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "status": "ok",
        **terms.as_dict(),
        **extra,
    }
    return record


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None, help="append JSONL records here")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    from repro.configs.base import all_arch_names, get_arch

    if args.all:
        targets = [
            (a, s) for a in all_arch_names() for s in get_arch(a).cells()
        ]
    else:
        if not args.arch or not args.shape:
            raise SystemExit("--arch and --shape required (or --all)")
        targets = [(args.arch, args.shape)]

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    existing = set()
    if args.out and args.skip_existing and os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    existing.add((r["arch"], r["shape"], r["mesh"]))
                except Exception:
                    pass

    for multi in meshes:
        mesh_name = "multi" if multi else "single"
        for arch_name, shape in targets:
            if (arch_name, shape, mesh_name) in existing:
                print(f"SKIP {arch_name} × {shape} × {mesh_name} (already done)")
                continue
            print(f"=== {arch_name} × {shape} × {mesh_name} ===", flush=True)
            try:
                rec = run_cell(arch_name, shape, multi)
                print(
                    f"  ok: compile={rec['compile_s']}s "
                    f"compute={rec['compute_s']:.3e}s memory={rec['memory_s']:.3e}s "
                    f"collective={rec['collective_s']:.3e}s dominant={rec['dominant']} "
                    f"useful={rec['useful_flops_ratio']:.2f}",
                    flush=True,
                )
                print(f"  memory_analysis: {rec['memory']}", flush=True)
            except Exception as e:
                rec = {
                    "arch": arch_name,
                    "shape": shape,
                    "mesh": mesh_name,
                    "status": "error",
                    "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-2000:],
                }
                print(f"  FAILED: {rec['error']}", flush=True)
            if args.out:
                os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
