"""A run whose timed path is broken underneath comes out not correct.

Each test drives a whole CPU rehearsal of a cell (everything but the
look for a chip) at the cell's own init, scaled to the rehearsal's depth,
with one fault of ``bench/faults.py`` planted in the program, and sees
``correct`` false; the sound run beside them sees it true.  A stale K/V
cache also reads above the chip's own limit of the cell here.
"""

import time

import pytest

from bench import cells as C
from bench import faults, harness

CELLS = ["qwen3-32b-tp8.chat-short", "internlm2-20b-tp8.batch"]


def rehearse(cell, fault=None, seed=5):
    with faults.planted(fault):
        return harness.run(cell, seed, 2.0, False,
                           t_start=time.perf_counter(), rehearse=True)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = rehearse(cell)
    assert res["correct"] is True, res["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_token_altered_where_produced(cell):
    res = rehearse(cell, "altered_token")
    assert res["correct"] is False, res["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_step_returns_its_state_unchanged(cell):
    res = rehearse(cell, "stale_kv")
    assert res["correct"] is False, res["checks"]
    chip_limit = C.cell(cell).check["logit_gap_mean_limit"]
    assert res["checks"]["logit_gap_mean"]["value"] > chip_limit


def test_faults_are_undone():
    from repro.models import transformer
    from repro.runtime import engine

    before = transformer.decode_step_paged, engine.sample_tokens
    for name in faults.FAULTS:
        with faults.planted(name):
            assert (transformer.decode_step_paged,
                    engine.sample_tokens) != before
    assert (transformer.decode_step_paged, engine.sample_tokens) == before
