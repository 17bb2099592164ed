import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
)
# ^ MUST be the very first lines, before ANY other import (including repro.*):
#   jax locks the device count on first init.
#
# Multi-pod dry-run: lower + compile every (architecture × input shape) on the
# 16×16 single-pod mesh AND the 2×16×16 multi-pod mesh; record memory/cost
# analysis + the collective schedule for §Roofline.
#
#   PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-405b --shape train_4k
#   PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod --out exp/dryrun

import argparse
import json
import re
import sys
import time
import traceback
from pathlib import Path

import jax

from repro.configs import registry
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.launch.steps import build_cell

# The chip the production meshes model (launch/mesh.py): a v5e pod. The host
# devices this script compiles on stand in for it.
TARGET_KIND = "TPU v5 lite"

COLLECTIVE_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)\b"
)


def hlo_collective_bytes(hlo_text: str) -> dict:
    """Sum output-shape bytes of every collective op in the (post-SPMD) HLO."""
    from repro.launch.hlo import collective_bytes

    return collective_bytes(hlo_text)


def run_cell(arch_name: str, shape_name: str, mesh, variant: str = "base",
             verbose: bool = True) -> dict:
    arch = registry.get(arch_name)
    cell = build_cell(arch, shape_name, mesh, variant=variant)
    t0 = time.time()
    lowered = cell.lower()
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = hlo_collective_bytes(hlo)

    n_dev = mesh.devices.size
    rec = {
        "arch": arch_name,
        "shape": shape_name,
        "variant": variant,
        "mesh": "x".join(str(mesh.shape[a]) for a in mesh.axis_names),
        "n_devices": int(n_dev),
        "device_kind": TARGET_KIND,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "flops": float(cost.get("flops", -1)) if cost else -1.0,
        "bytes_accessed": float(cost.get("bytes accessed", -1)) if cost else -1.0,
        "collectives": coll,
        "memory": {
            k: int(getattr(mem, k, 0))
            for k in (
                "argument_size_in_bytes",
                "output_size_in_bytes",
                "temp_size_in_bytes",
                "generated_code_size_in_bytes",
            )
        }
        if mem
        else {},
    }
    if verbose:
        args_gb = rec["memory"].get("argument_size_in_bytes", 0) / 1e9
        temp_gb = rec["memory"].get("temp_size_in_bytes", 0) / 1e9
        print(
            f"[OK] {arch_name}/{shape_name}/{variant} mesh={rec['mesh']} "
            f"lower={t_lower:.0f}s compile={t_compile:.0f}s "
            f"flops={rec['flops']:.3e} args={args_gb:.1f}GB temp={temp_gb:.1f}GB "
            f"coll_bytes={sum(v for k, v in coll.items() if not k.startswith('_')):.3e}",
            flush=True,
        )
    return rec


def all_cells():
    """Every (arch, shape[, variant]) cell in the assignment + paper-native."""
    cells = []
    for name, arch in registry.ARCHS.items():
        for s in arch.shapes:
            cells.append((name, s.name, "base"))
            if s.dims.get("landmark_variant"):
                cells.append((name, s.name, "landmark"))
    return cells


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--variant", default="base")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--debug-mesh", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--skip-paper-native", action="store_true")
    args = ap.parse_args(argv)

    mesh = (
        make_debug_mesh(multi_pod=args.multi_pod)
        if args.debug_mesh
        else make_production_mesh(multi_pod=args.multi_pod)
    )
    print(f"mesh axes={mesh.axis_names} shape={tuple(mesh.shape[a] for a in mesh.axis_names)}",
          flush=True)

    if args.all:
        cells = all_cells()
        if args.skip_paper_native:
            cells = [c for c in cells if registry.get(c[0]).family != "cf"]
    else:
        cells = [(args.arch, args.shape, args.variant)]

    records, failures = [], []
    for arch_name, shape_name, variant in cells:
        try:
            records.append(run_cell(arch_name, shape_name, mesh, variant))
        except Exception as e:  # noqa: BLE001 — a failed cell is a bug to report
            failures.append((arch_name, shape_name, variant, repr(e)))
            print(f"[FAIL] {arch_name}/{shape_name}/{variant}: {e}", flush=True)
            traceback.print_exc()

    if args.out:
        out = Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        tag = "multipod" if args.multi_pod else "singlepod"
        (out / f"dryrun_{tag}.json").write_text(json.dumps(records, indent=1))
        print(f"wrote {out}/dryrun_{tag}.json ({len(records)} cells)")
    if failures:
        print(f"{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        sys.exit(1)
    print(f"all {len(records)} cells compiled OK")


if __name__ == "__main__":
    main()
