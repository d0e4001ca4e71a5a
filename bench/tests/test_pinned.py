"""The dense cells' weights and reference logits, pinned by digest.

At each cell's CPU rehearsal size (``bench.cells.rehearsal``), for a
fixed seed: a digest of every leaf of ``weights.make_all``, and of the
float32 ``reference.forward`` logits (and the fp8 control's) at fixed
rows of two fixed token sequences.  The digests were computed at commit
62b1170, before the dense layer moved behind ``bench/arch/``, from a
checkout of that commit by the same arithmetic as ``digest`` below (its
``make_all`` then returned the one kind's leaves under ``layer``).  A
change that moves a value of the dense path, or of the reference, fails
here.
"""

import hashlib

import numpy as np
import pytest

from bench import cells as C
from bench import reference
from bench import weights as W

SEED = 2 ** 33 + 17
PINNED = {
    "qwen3-32b-tp8.chat-short": {
        "weights": "3a602f96b9a3235d",
        "logits": "49d150d58e89330e",
        "control": "ecd260488671a44b",
    },
    "internlm2-20b-tp8.batch": {
        "weights": "28be8ce21d3e6669",
        "logits": "cc2421d84de18340",
        "control": "6d9ef6ac03bfd215",
    },
}


def digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(str((a.dtype.name, a.shape)).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


def seqs_rows(vocab):
    rng = np.random.default_rng(11)
    seqs = [rng.integers(0, vocab, 37).astype(np.int32),
            rng.integers(0, vocab, 300).astype(np.int32)]
    return seqs, [np.array([0, 17, 36]), np.array([5, 150, 299])]


@pytest.mark.parametrize("name", sorted(PINNED))
def test_weights_are_those_pinned(name):
    cell = C.rehearsal(C.cell(name))
    w = W.make_all(cell.arch, cell.cfg, SEED)
    leaves = [w["top"][k] for k in sorted(w["top"])]
    for kind in w["layers"]:
        leaves += [w["layers"][kind][k] for k in sorted(w["layers"][kind])]
    assert digest(leaves) == PINNED[name]["weights"]


@pytest.mark.parametrize("control", [False, True], ids=["logits", "control"])
@pytest.mark.parametrize("name", sorted(PINNED))
def test_reference_logits_are_those_pinned(name, control):
    cell = C.rehearsal(C.cell(name))
    seqs, rows = seqs_rows(cell.cfg["vocab_size"])
    out = reference.forward(cell.arch, cell.cfg, SEED, seqs, rows,
                            control=control)
    assert digest(out) == PINNED[name]["control" if control else "logits"]
