"""Readings behind a cell's correctness limit, several runs in one process.

    python3 bench/limits.py --workload <cell> --seconds <s> --seeds a,b,c \
        --mode sound [--mode control --mode stale_kv ...] [--rehearse]

Each mode runs the cell once per seed, as ``bench/run.py`` does, and
prints one JSON line: the mean gap compared, its limit, and ``correct``.
``sound`` is the program as it is; ``control`` puts the fp8 reference in
the program's place (and gives the program's own reading of the same
run beside it); a fault name (``bench/faults.py``) plants that fault in
the program.  For setting limits, not for timing.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--mode", action="append", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from bench import faults, harness

    for mode in args.mode:
        for seed in (int(s) for s in args.seeds.split(",")):
            t = time.perf_counter()
            with faults.planted(None if mode in ("sound", "control")
                                else mode):
                res = harness.run(args.workload, seed, args.seconds, False,
                                  t_start=t, rehearse=args.rehearse,
                                  control=mode == "control")
            gap = res["checks"]["logit_gap_mean"]
            line = {"workload": args.workload, "mode": mode, "seed": seed,
                    "value": gap["value"], "limit": gap["limit"],
                    "correct": res["correct"],
                    "wall_s": time.perf_counter() - t}
            if mode == "control":
                line["program"] = res["control"]["program_mean_gap"]
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
