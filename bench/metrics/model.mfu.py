"""Model FLOP utilisation of the window (%): the model FLOPs of the
prompt and output tokens processed (weight matmuls, the attention the
algorithm needs, one LM head row per decode row and per chunk) over the
window times the chip's bf16 peak."""

from bench import work


def read(r):
    if r.peak is None or not r.decode_rows and not r.chunks:
        return None
    flops = work.model_flops(r, "all")
    return 100.0 * flops / (r.seconds * r.peak["bf16_flops"]) if flops else None
