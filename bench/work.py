"""The work an algorithm needs, counted from the lengths served.

These counts are the yardstick of the roofline and utilisation metrics.
They count what attention over the held heads needs, whatever kernel
computes it: no extra pass over the keys, no padding columns of a block
table, no upcast.  Each key of a row is read once (K and V), each query
row read once and each output row written once, in the model's dtype.
The counts here are one layer's; a configuration's architecture module
(``bench/arch/<name>.py``) sums them over its layers, and counts its own
weight-matmul and LM-head FLOPs.
"""

from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {PEAKS.name}")
    return table[device_kind]


def decode_row(kv_len: int, dims: dict, kv_bytes: int = 2,
               io_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one slot's decode attention in one layer: one
    query row per head over ``kv_len`` keys."""
    H, KV, Dh = dims["heads"], dims["kv_heads"], dims["head_dim"]
    flops = 4.0 * H * Dh * kv_len
    nbytes = kv_len * KV * Dh * 2 * kv_bytes + 2 * H * Dh * io_bytes
    return flops, nbytes


def prefill_chunk(q_start: int, n: int, dims: dict, kv_bytes: int = 2,
                  io_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one chunk's attention in one layer: rows at
    positions ``q_start .. q_start+n-1``, each over its causal keys."""
    H, KV, Dh = dims["heads"], dims["kv_heads"], dims["head_dim"]
    keys = n * q_start + n * (n + 1) // 2
    flops = 4.0 * H * Dh * keys
    nbytes = (q_start + n) * KV * Dh * 2 * kv_bytes + 2 * n * H * Dh * io_bytes
    return flops, nbytes


def roofline_s(flops: float, nbytes: float, peak: dict) -> float:
    """Least time for the work: the larger of its compute and memory bounds."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])


def window_decode(r) -> tuple[float, float, int]:
    """(FLOPs, bytes, rows) of the decode attention dispatched in the
    window of readings ``r``, over all layers."""
    f = b = 0.0
    n = 0
    for t, kv in r.decode_rows:
        if r.in_window(t):
            df, db = r.arch.decode_attention(kv, r.dims)
            f, b, n = f + df, b + db, n + 1
    return f, b, n


def window_prefill(r) -> tuple[float, float, int, int]:
    """(FLOPs, bytes, chunks, prompt tokens) of the prefill attention
    dispatched in the window, over all layers."""
    f = b = 0.0
    n = toks = 0
    for t, start, m in r.chunks:
        if r.in_window(t):
            df, db = r.arch.prefill_attention(start, m, r.dims)
            f, b, n, toks = f + df, b + db, n + 1, toks + m
    return f, b, n, toks


def model_flops(r, phase: str) -> float:
    """Model FLOPs of the window's work: ``decode`` (one token per slot
    row, with its LM head), ``prefill`` (every prompt token, one LM head
    row per chunk) or ``all``."""
    per_tok = r.arch.matmul_flops_per_token(r.dims)
    head = r.arch.head_flops(r.dims)
    total = 0.0
    if phase in ("decode", "all"):
        f, _, rows = window_decode(r)
        total += f + rows * (per_tok + head)
    if phase in ("prefill", "all"):
        f, _, chunks, toks = window_prefill(r)
        total += f + toks * per_tok + chunks * head
    return total
