"""Find a mix's knee: the highest offered rate the system sustains.

    python3 bench/sweep.py --workload <cell> --rates 0.5,1,1.5 --seconds 30

Runs the cell once per rate, in one process (the chip's programs compile
once), and prints for each rate the output tokens per second, the TTFT
tail and the requests waiting for a slot at the window's opening and
close.  The knee is the highest rate at which that queue does not grow
over the window.  A cell's mix then fixes its rate at about four fifths
of the knee; the benchmark itself never searches.
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


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from bench import harness

    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        t0 = time.perf_counter()
        res = harness.run(args.workload, args.seed + i, args.seconds, False,
                          t_start=t0, mix_override={"rate_rps": rate})
        m = {k: v["value"] for k, v in res["metrics"].items()}
        print(json.dumps({"rate_rps": rate, "waiting": res["waiting"],
                          "correct": res["correct"], **m}), flush=True)


if __name__ == "__main__":
    main()
