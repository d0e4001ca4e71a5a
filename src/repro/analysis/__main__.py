"""``python -m repro.analysis`` — compiled-artifact contract checker.

Modes:

* ``--check-all``      single-device contracts (including the kernel-guard
                       contracts), plus the TP contracts in a
                       ``--xla_force_host_platform_device_count=4``
                       subprocess (or inline when >= 4 devices are
                       already visible).
* ``--check-kernels``  only the static kernel guard (VMEM working sets,
                       grid coverage, Σ-overflow bounds, LUT census) —
                       no tracing or compilation.
* ``--single-only`` / ``--tp-only``  restrict to one half (the CI matrix
                       and the self-spawned subprocess use these).
* ``--json PATH|-``    write the machine-readable report (``-`` = stdout).
* ``--update``         rewrite the committed report(s):
                       ``ANALYSIS_contracts.json`` under ``--check-all``,
                       ``ANALYSIS_kernels.json`` whenever the kernel
                       guard ran.
* ``--diff PATH``      ratchet against a committed contracts report:
                       violations may only decrease, contracts may not
                       disappear.
* ``--diff-kernels PATH``  ratchet against a committed kernels report:
                       overflow bounds may not shrink, LUT/VMEM bytes
                       and budgets may not regress.

Exit codes: 0 all contracts hold (and ratchets pass), 1 contract
violations, 2 ratchet regression or harness failure.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys


def _repo_root() -> pathlib.Path:
    # src/repro/analysis/__main__.py -> repo root is parents[3]
    return pathlib.Path(__file__).resolve().parents[3]


def _run_tp_subprocess(devices: int) -> dict:
    """Self-spawn the TP half under a forced multi-device CPU."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # host devices; the chip is the parent's
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={devices}"
                        ).strip()
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (str(_repo_root() / "src"),
                    env.get("PYTHONPATH", "")) if p)
    out = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--check-all",
         "--tp-only", "--json", "-"],
        env=env, capture_output=True, text=True, timeout=900)
    if out.returncode not in (0, 1):
        raise RuntimeError(
            f"TP contract subprocess failed (rc={out.returncode}):\n"
            f"{out.stdout}\n{out.stderr}")
    return json.loads(out.stdout)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python -m repro.analysis",
                                 description=__doc__.splitlines()[0])
    ap.add_argument("--check-all", action="store_true",
                    help="evaluate the contract suite")
    ap.add_argument("--check-kernels", action="store_true",
                    help="evaluate the static kernel guard only")
    ap.add_argument("--single-only", action="store_true",
                    help="only the single-device contracts")
    ap.add_argument("--tp-only", action="store_true",
                    help="only the 4-way-mesh contracts (needs >= 4 "
                         "devices; --check-all self-spawns them otherwise)")
    ap.add_argument("--json", metavar="PATH",
                    help="write the report as JSON ('-' for stdout)")
    ap.add_argument("--update", action="store_true",
                    help=f"rewrite the committed report "
                         f"(ANALYSIS_contracts.json)")
    ap.add_argument("--diff", metavar="PATH",
                    help="ratchet the fresh report against a committed one")
    ap.add_argument("--diff-kernels", metavar="PATH",
                    help="ratchet the fresh kernel-guard report against a "
                         "committed ANALYSIS_kernels.json")
    ap.add_argument("--devices", type=int, default=4,
                    help="forced device count for the TP half (default 4)")
    args = ap.parse_args(argv)
    if not (args.check_all or args.check_kernels):
        ap.error("nothing to do: pass --check-all and/or --check-kernels")
    if args.single_only and args.tp_only:
        ap.error("--single-only and --tp-only are mutually exclusive")
    if args.check_kernels and not args.check_all and args.tp_only:
        ap.error("--check-kernels has no TP half; drop --tp-only")

    # The kernel guard runs on the main process only — the TP subprocess
    # would just recompute identical, device-count-independent facts.
    kernel_report = None
    if not args.tp_only:
        from repro.analysis import kernel_guard
        kernel_report = kernel_guard.check_kernels()

    if not args.check_all:
        return _kernels_only(args, kernel_report)

    from repro.analysis import contracts

    reports = []
    if not args.tp_only:
        reports.append(contracts.build_report(
            contracts.single_device_contracts()
            + contracts.kernel_contracts(kernel_report)))
    if not args.single_only:
        import jax
        if len(jax.devices()) >= 4:
            reports.append(contracts.build_report(contracts.tp_contracts()))
        elif args.tp_only:
            print("error: --tp-only needs >= 4 devices "
                  "(set XLA_FLAGS=--xla_force_host_platform_device_count=4)",
                  file=sys.stderr)
            return 2
        else:
            reports.append(_run_tp_subprocess(args.devices))
    report = contracts.merge_reports(*reports)

    text = json.dumps(report, indent=2, sort_keys=True)
    if args.json == "-":
        print(text)
    else:
        if args.json:
            pathlib.Path(args.json).write_text(text + "\n")
        for c in report["contracts"]:
            mark = "ok " if c["status"] == "ok" else "FAIL"
            print(f"[{mark}] {c['name']}", file=sys.stderr)
            for v in c["violations"]:
                print(f"       {v}", file=sys.stderr)
    if args.update:
        contracts.dump_report(report, str(_repo_root() / contracts.REPORT_NAME))
        print(f"wrote {contracts.REPORT_NAME}", file=sys.stderr)
        if kernel_report is not None:
            from repro.analysis import kernel_guard
            kernel_guard.dump_report(
                kernel_report, str(_repo_root() / kernel_guard.REPORT_NAME))
            print(f"wrote {kernel_guard.REPORT_NAME}", file=sys.stderr)

    rc = 0 if report["n_violations"] == 0 else 1
    problems = []
    if args.diff:
        problems += contracts.ratchet_violations(
            contracts.load_report(args.diff), report)
    if args.diff_kernels and kernel_report is not None:
        from repro.analysis import kernel_guard
        problems += kernel_guard.ratchet_violations(
            kernel_guard.load_report(args.diff_kernels), kernel_report)
    for p in problems:
        print(p, file=sys.stderr)
    if problems:
        return 2
    return rc


def _kernels_only(args, kernel_report: dict) -> int:
    """``--check-kernels`` without ``--check-all``: guard-only mode."""
    from repro.analysis import kernel_guard

    text = json.dumps(kernel_report, indent=2, sort_keys=True)
    if args.json == "-":
        print(text)
    else:
        if args.json:
            pathlib.Path(args.json).write_text(text + "\n")
        for name, p in sorted(kernel_report["policies"].items()):
            mark = "ok " if not p["violations"] else "FAIL"
            print(f"[{mark}] policy {name}: lut_bytes={p['lut_bytes']} "
                  f"max_lk={p['max_lk']} margin={p['margin']}",
                  file=sys.stderr)
            for v in p["violations"]:
                print(f"       {v}", file=sys.stderr)
        for name, k in sorted(kernel_report["kernels"].items()):
            mark = "ok " if k["status"] == "ok" else "FAIL"
            extra = (f"vmem_bytes={k['vmem_bytes']}" if k["kind"] == "pallas"
                     else "shard_map")
            print(f"[{mark}] kernel {name}: {extra}", file=sys.stderr)
            for v in k["violations"]:
                print(f"       {v}", file=sys.stderr)
        for v in kernel_report["violations"]:
            print(f"[FAIL] {v}", file=sys.stderr)
    if args.update:
        kernel_guard.dump_report(
            kernel_report, str(_repo_root() / kernel_guard.REPORT_NAME))
        print(f"wrote {kernel_guard.REPORT_NAME}", file=sys.stderr)

    rc = 0 if kernel_report["n_violations"] == 0 else 1
    if args.diff_kernels:
        problems = kernel_guard.ratchet_violations(
            kernel_guard.load_report(args.diff_kernels), kernel_report)
        for p in problems:
            print(p, file=sys.stderr)
        if problems:
            return 2
    return rc


if __name__ == "__main__":
    # a CPU-only tool: compile on the host, never take the accelerator
    # (one process owns it) — the TP half's child is pinned the same way
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.exit(main())
