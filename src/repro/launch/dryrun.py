import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"

# ^ MUST precede every other import: jax locks the device count on first
# initialization, and the production meshes need 512 placeholder devices —
# host devices, never the accelerator, which belongs to one process.

"""Multi-pod dry-run: lower + compile every (arch × shape) cell on the
single-pod 16×16 and multi-pod 2×16×16 meshes; record memory_analysis and
cost_analysis (EXPERIMENTS.md §Dry-run).

Usage:
  python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out results/dryrun]
"""

import argparse
import json
import time
import traceback

import jax

from repro.configs import ARCHS, SHAPES, shape_applicable, get_arch
from repro.launch.cells import build_cell, lower_cell
from repro.analysis import collectives_summary
from repro.launch.mesh import make_production_mesh

HBM_PER_CHIP = 16 * 1024**3  # TPU v5e: 16 GiB


def memory_summary(compiled) -> dict:
    ma = compiled.memory_analysis()
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        out[k] = int(getattr(ma, k, 0) or 0)
    # arguments are donated/aliased for states; live set ≈ args + temps
    out["live_bytes"] = (out["argument_size_in_bytes"]
                         + out["temp_size_in_bytes"])
    out["fits_hbm_16g"] = out["live_bytes"] <= HBM_PER_CHIP
    return out


def run_cell(arch_name: str, shape_name: str, multi_pod: bool) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {
        "arch": arch_name, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": mesh.size,
    }
    arch = get_arch(arch_name)
    ok, reason = shape_applicable(arch, SHAPES[shape_name])
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec
    t0 = time.time()
    try:
        cell = build_cell(arch_name, shape_name, mesh)
        lowered = lower_cell(cell)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        cost = compiled.cost_analysis()
        rec.update(
            status="ok",
            lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            memory=memory_summary(compiled),
            cost={
                "flops": float(cost.get("flops", 0.0)),
                "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
            },
            collectives=collectives_summary(compiled.as_text()),
            params=cell.arch.param_count(),
            params_active=cell.arch.param_count(active_only=True),
        )
    except Exception as exc:  # noqa: BLE001 — reported per cell
        rec.update(status="error", error=f"{type(exc).__name__}: {exc}",
                   traceback=traceback.format_exc()[-2000:])
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None, help="directory for per-cell json")
    args = ap.parse_args()

    cells = ([(a, s) for a in sorted(ARCHS) for s in SHAPES]
             if args.all else [(args.arch, args.shape)])
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    for arch_name, shape_name in cells:
        for mp in meshes:
            rec = run_cell(arch_name, shape_name, mp)
            tag = f"{arch_name} × {shape_name} × {rec['mesh']}"
            if rec["status"] == "ok":
                mem = rec["memory"]
                print(f"[OK]   {tag}: compile {rec['compile_s']}s, "
                      f"live {mem['live_bytes']/2**30:.2f} GiB/dev "
                      f"(fits={mem['fits_hbm_16g']}), "
                      f"flops {rec['cost']['flops']:.3e}")
            elif rec["status"] == "skipped":
                print(f"[SKIP] {tag}: {rec['reason']}")
            else:
                print(f"[ERR]  {tag}: {rec['error']}")
            if args.out:
                os.makedirs(args.out, exist_ok=True)
                fname = (f"{arch_name}__{shape_name}__{rec['mesh']}.json"
                         .replace("/", "_"))
                with open(os.path.join(args.out, fname), "w") as f:
                    json.dump(rec, f, indent=1)


if __name__ == "__main__":
    main()
