import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")
os.environ["JAX_PLATFORMS"] = "cpu"

# ^ must precede jax import: the probes lower against the production mesh
# of host devices, and never take the accelerator.

"""Roofline analysis — §Roofline of EXPERIMENTS.md.

Three terms per (arch × shape), single-pod 16×16 mesh, TPU v5e constants:

    compute_s    = HLO_FLOPs_per_chip / 197e12            (bf16 MXU peak)
    memory_s     = HLO_bytes_per_chip / 819e9              (HBM BW)
    collective_s = wire_bytes_per_chip / 50e9              (ICI, ring model)

Methodology (probe extrapolation): XLA's cost_analysis counts a while-loop
body ONCE (verified empirically: a scan of 10 matmuls reports 1× the
flops), so the real scan-over-periods program cannot be costed directly.
We lower two UNROLLED probes at depth 1 and 2 periods (naive attention —
no internal scans) and extrapolate linearly:

    T(L) = U(1) + (L − 1) · (U(2) − U(1))

which is exact for a homogeneous period stack: the depth-independent base
(embedding, LM head, loss, data movement) and the per-period cost both
appear exactly once in the difference.  Collective wire bytes use the
same extrapolation, with group-size-aware ring formulas (hlo_analysis).

MODEL_FLOPS = 6·N·tokens (train) or 2·N_active·tokens (serving); the
ratio MODEL_FLOPS / HLO_FLOPs measures how much compiled compute is
"useful" (catches remat/recompute and attention/dispatch overheads).

Usage:
  PYTHONPATH=src python -m benchmarks.roofline --all --out results/roofline
  PYTHONPATH=src python -m benchmarks.roofline --arch qwen3-32b --shape train_4k
"""

import argparse
import dataclasses
import json
import traceback

PEAK_FLOPS = 197e12      # bf16 per chip
HBM_BW = 819e9           # bytes/s per chip
ICI_BW = 50e9            # bytes/s per link (per-chip wire bytes / this)


@dataclasses.dataclass
class Terms:
    flops: float          # per-chip
    bytes_hbm: float      # per-chip
    wire_bytes: float     # per-chip
    coll_counts: dict


def measure(cell) -> Terms:
    from repro.launch.cells import lower_cell
    from repro.analysis import parse_collectives
    lowered = lower_cell(cell)
    compiled = lowered.compile()
    cost = compiled.cost_analysis()
    coll = parse_collectives(compiled.as_text())
    return Terms(
        flops=float(cost.get("flops", 0.0)),
        bytes_hbm=float(cost.get("bytes accessed", 0.0)),
        wire_bytes=coll["total"].wire_bytes,
        coll_counts={k: {"count": v.count,
                         "wire_gb": round(v.wire_bytes / 1e9, 2)}
                     for k, v in coll.items() if v.count},
    )


def extrapolate(u1: Terms, u2: Terms, n_periods: int) -> Terms:
    def ext(a, b):
        return a + (n_periods - 1) * max(b - a, 0.0)
    return Terms(
        flops=ext(u1.flops, u2.flops),
        bytes_hbm=ext(u1.bytes_hbm, u2.bytes_hbm),
        wire_bytes=ext(u1.wire_bytes, u2.wire_bytes),
        coll_counts=u2.coll_counts,
    )


def model_flops(arch, shape) -> float:
    n_active = arch.param_count(active_only=True)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch  # decode: 1 token/seq


def analyze_cell(arch_name: str, shape_name: str, mesh,
                 run_overrides: dict | None = None) -> dict:
    from repro.configs import SHAPES, get_arch, shape_applicable
    from repro.launch.cells import build_cell

    arch = get_arch(arch_name)
    shape = SHAPES[shape_name]
    rec = {"arch": arch_name, "shape": shape_name, "n_devices": mesh.size}
    ok, reason = shape_applicable(arch, shape)
    if not ok:
        rec.update(status="skipped", reason=reason)
        return rec
    try:
        cells = [build_cell(arch_name, shape_name, mesh, probe=True,
                            probe_periods=p, run_overrides=run_overrides)
                 for p in (1, 2)]
        u1, u2 = measure(cells[0]), measure(cells[1])
        t = extrapolate(u1, u2, arch.n_periods)
        mf = model_flops(arch, shape)
        hlo_global = t.flops * mesh.size
        terms = {
            "compute_s": t.flops / PEAK_FLOPS,
            "memory_s": t.bytes_hbm / HBM_BW,
            "collective_s": t.wire_bytes / ICI_BW,
        }
        dominant = max(terms, key=terms.get)
        bound_s = terms[dominant]
        rec.update(
            status="ok",
            terms={k: round(v, 6) for k, v in terms.items()},
            dominant=dominant,
            step_time_lower_bound_s=round(bound_s, 6),
            roofline_fraction=round(
                (t.flops / PEAK_FLOPS) / bound_s, 4) if bound_s else None,
            model_flops=mf,
            hlo_flops_global=hlo_global,
            useful_flops_ratio=round(mf / hlo_global, 4) if hlo_global else 0,
            per_chip={"flops": t.flops, "bytes_hbm": t.bytes_hbm,
                      "wire_bytes": t.wire_bytes},
            collective_ops=t.coll_counts,
            probes={"u1_flops": u1.flops, "u2_flops": u2.flops},
            n_periods=arch.n_periods,
        )
    except Exception as exc:  # noqa: BLE001
        rec.update(status="error", error=f"{type(exc).__name__}: {exc}",
                   traceback=traceback.format_exc()[-1500:])
    return rec


def main() -> None:
    from repro.configs import ARCHS, SHAPES
    from repro.launch.mesh import make_production_mesh

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--override", default=None,
                    help="json dict of RunConfig overrides (hillclimbing)")
    ap.add_argument("--tag", default="baseline")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=False)
    overrides = json.loads(args.override) if args.override else None
    cells = ([(a, s) for a in sorted(ARCHS) for s in SHAPES]
             if args.all else [(args.arch, args.shape)])

    for arch_name, shape_name in cells:
        rec = analyze_cell(arch_name, shape_name, mesh, overrides)
        rec["tag"] = args.tag
        tag = f"{arch_name} × {shape_name}"
        if rec["status"] == "ok":
            t = rec["terms"]
            print(f"[OK]   {tag}: compute {t['compute_s']*1e3:.2f}ms | "
                  f"memory {t['memory_s']*1e3:.2f}ms | "
                  f"collective {t['collective_s']*1e3:.2f}ms → "
                  f"{rec['dominant']} bound; useful-flops "
                  f"{rec['useful_flops_ratio']:.2f}")
        elif rec["status"] == "skipped":
            print(f"[SKIP] {tag}: {rec['reason']}")
        else:
            print(f"[ERR]  {tag}: {rec['error']}")
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            fname = f"{arch_name}__{shape_name}__{args.tag}.json".replace(
                "/", "_")
            with open(os.path.join(args.out, fname), "w") as f:
                json.dump(rec, f, indent=1)


if __name__ == "__main__":
    main()
