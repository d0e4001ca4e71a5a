"""Chip benchmark: one run of one cell, one JSON line of results.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine whose TPU no other process
holds.  The cell is looked up by name in ``BENCHMARK.json``.  The run
makes its weights and traffic from ``--seed``, warms up every program the
window uses (set-up), serves the cell's traffic mix through the program's
streaming server for ``--seconds``, frees the program, and checks the
tokens it served against the plain float32 reference.  With ``--trace 0``
the metrics are the cell's end-to-end metrics; with ``--trace 1`` the
window is traced and the metrics are its per-layer ones.

Lines on standard error say what fed each number; the last line of
standard output is the result object.  ``--rehearse`` runs the same cell
code on the CPU at a tiny width, and reports no device metric.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at a tiny width (no device metric)")
    ap.add_argument("--control", action="store_true",
                    help="put the fp8 control in the program's place in "
                         "the comparison (for setting the limit; not for "
                         "timing)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from bench import harness

    result = harness.run(args.workload, args.seed, args.seconds,
                         bool(args.trace), t_start=T_START,
                         rehearse=args.rehearse, control=args.control,
                         trace_dir=ROOT / "bench" / ".trace")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
