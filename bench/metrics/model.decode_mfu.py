"""Share of the chip's bf16 peak (%) that the decode-step programs reach
while they run: the model FLOPs of the decode rows dispatched in the
window over the device time of the window's decode programs.  It bounds
the decode kernel's roofline share from the whole step's side."""

from bench import trace as T
from bench import work

PROGRAM = r"jit_decode_sampled_fn"


def read(r):
    if r.trace is None or r.peak is None:
        return None
    s, n = T.seconds(r.trace.modules, PROGRAM, r.trace)
    flops = work.model_flops(r, "decode")
    return 100.0 * flops / (s * r.peak["bf16_flops"]) if s and flops else None
