"""The control, put in the program's place, comes out not correct.

The control is the reference computed one precision step below the
bfloat16 that the configurations state: fp8 (e4m3) operands in every
matmul.  At each position of the served tokens it picks its own best
token, and that token's gap below the float32 reference's best is the
number compared.  Here at the CPU rehearsal's size and limit, on three
seeds; the chip readings at the cells' own sizes are in PERF.md and
``bench/checks/<cell>.json``.
"""

import time

import pytest

from bench import harness

CELLS = ["qwen3-32b-tp8.chat-short", "internlm2-20b-tp8.batch"]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_comes_out_not_correct(cell, seed):
    res = harness.run(cell, seed, 2.0, False, t_start=time.perf_counter(),
                      rehearse=True, control=True)
    gap = res["checks"]["logit_gap_mean"]
    assert res["correct"] is False, gap
    assert gap["value"] == res["control"]["control_mean_gap"] > gap["limit"]
    # the program's own reading of the same run passes the same limit
    assert res["control"]["program_mean_gap"] <= gap["limit"]
