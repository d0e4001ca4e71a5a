"""Share of the chip's bf16 peak (%) that the prefill-chunk programs
reach while they run: the model FLOPs of the chunks dispatched in the
window over the device time of the window's chunk programs."""

from bench import trace as T
from bench import work

PROGRAM = r"jit_chunk(_sampled)?_fn"


def read(r):
    if r.trace is None or r.peak is None:
        return None
    s, n = T.seconds(r.trace.modules, PROGRAM, r.trace)
    flops = work.model_flops(r, "prefill")
    return 100.0 * flops / (s * r.peak["bf16_flops"]) if s and flops else None
