"""Chip smoke test: the continuous-batching serving path on a TPU.

Run from the root of the repository, on a machine with a TPU, with no
other process using it:

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # tensor-parallel serving, 4 chips

One chip runs four phases, each printing its own lines:

* device — fails unless JAX's first device is a TPU (there is no CPU
  path: run elsewhere, the script exits non-zero);
* parity — the compiled paged-decode and paged-prefill kernels against
  the dense references on one seeded random-normal pool at qwen3-32b
  attention widths (H=64, KVH=8, Dh=128, 16-token pages), with a
  permuted block table and ragged cursors, for exact, rexp/uint8 and
  lut2d/uint8 over the f32 and int8 pools;
* serve — ``repro.launch.serve.main`` at qwen3-32b's published widths
  cut to two layers, pipelined engine behind the streaming server, both
  paged kernels forced (``--paged-backend pallas``), once with
  rexp/uint8 and once with exact softmax;
* backends — the same rexp/uint8 requests through the compiled kernels
  and through the dense reference, their served logits compared.

``--four-chips`` runs only the same requests at ``--tp 4`` (the 'heads'
regime for qwen3-32b's 8 KV heads, weights TP-sharded) and at ``--tp 1``
through the dense reference, in the same process, and compares their
served logits.

Any failed check exits non-zero.  The last line of standard output is
one JSON object: ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

enable_compile_cache()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

#: qwen3-32b attention widths (configs/qwen3_32b.py) and serving page size
H, KVH, DH, PS = 64, 8, 128, 16
#: parity problem: 4 slots × 16 pages (256 tokens), 16-token chunks
B, MP, CHUNK = 4, 16, 16
DECODE_LENS = (256, 1, 113, 190)      # keys per slot, incl. the new token
PREFILL_START = (0, 100, 240, 37)     # tokens cached before the chunk
PREFILL_CHUNK = (16, 9, 16, 1)        # valid tokens in each slot's chunk

SERVE_ARGS = ["--arch", "qwen3-32b", "--scale-down", "none",
              "--periods", "2", "--batch", "4", "--prompt-len", "256",
              "--new-tokens", "32", "--seed", "0"]
SOFTMAX_ARGS = {"rexp": ["--softmax", "rexp", "--precision", "uint8"],
                "exact": ["--softmax", "exact"]}
#: the serving phases must start from an empty device: each builds its
#: own 10 GB of weights, and a leaked copy of the last one would not fit
MAX_LIVE_BYTES_BETWEEN_PHASES = 1 << 30

#: served logits of two runs of the same requests (kernels vs dense
#: reference; tp=4 vs tp=1) agree to this fraction of their span.  Both
#: sides attend at f32, so they differ by roundoff (reassociated sums),
#: and a LUT bin flip at a rounding edge moves one attention weight by
#: one table step; the first four-chip run read 1.6e-3–2.7e-3 for first
#: tokens, and this is about 10× that.  A misread page, head or scale, or
#: a wrongly sharded pool, moves logits by the order of their span.
LOGIT_TOL = 0.03


def _compile_seconds():
    """Sum of XLA backend-compile durations in this process."""
    total = [0.0, 0]

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            total[0] += duration
            total[1] += 1

    jax.monitoring.register_event_duration_secs_listener(listen)
    return total


# ---------------------------------------------------------------------------
# Phase: device
# ---------------------------------------------------------------------------


def phase_device(min_count: int = 1) -> dict:
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        sys.exit(f"device: no TPU found — JAX's first device is "
                 f"{dev.platform!r}; this smoke test runs only on a TPU")
    if len(devices) < min_count:
        sys.exit(f"device: {len(devices)} TPU device(s), need {min_count}")
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}",
          flush=True)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


# ---------------------------------------------------------------------------
# Phase: kernel parity
# ---------------------------------------------------------------------------


#: parity tolerance, a fraction of max|v|.  Kernel and reference both
#: compute their dots at f32 (``kernels.common.ATTN_PRECISION``), so the
#: integer LUT pipeline agrees bin for bin and an output moves only by
#: f32 accumulation over ≤ 256 keys (≲ 256·2⁻²⁴ ≈ 1.5e-5).  A kernel
#: whose dots ran one bf16 pass would round every value by up to
#: 2⁻⁹ — 8× this limit — and move logits across LUT bins.
PARITY_TOL = 2.0 ** -12
#: a LUT row with a logit within this distance (in logit units) of a bin's
#: rounding edge may take the neighbouring bin on one side — f32 roundoff
#: of a 128-term dot of N(0, 1) values is ~1e-6 — and then differs by one
#: table step.  Such rows are counted and reported, not held to the limit.
EDGE_LOGIT = 2e-5


def _parity_problem(seed: int = 0):
    """One seeded pool shared by every case: q, k and v are standard
    normal, as a model's projections are at init, so logits cover the
    LUT bins densely and every value carries a full f32 mantissa.  The
    int8 pool holds the same K/V quantized per (page, head, token) row."""
    from repro.core.quantization import quantize_rows
    from repro.runtime.paged_cache import pool_shape

    n_pages = 1 + B * MP
    shape = pool_shape(n_pages, PS, KVH, DH)
    rng = np.random.default_rng(seed)
    k = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    v = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    # int8 pool: per-(page, head, token) scales, laid out as the pool's
    # head-major scale rows (runtime.paged_cache.scale_pool_shape)
    (k8, ks), (v8, vs) = quantize_rows(k), quantize_rows(v)
    ks = ks.reshape(n_pages, 1, KVH * PS)
    vs = vs.reshape(n_pages, 1, KVH * PS)
    bt = rng.permutation(np.arange(1, n_pages)).reshape(B, MP)

    def queries(rows):
        return jnp.asarray(rng.standard_normal((B, H, rows, DH)), jnp.float32)

    return dict(q_decode=queries(1), q_prefill=queries(CHUNK),
                f32=(k, v, {}), int8=(k8, v8, dict(k_scales=ks, v_scales=vs)),
                bt=jnp.asarray(bt, jnp.int32))


def _edge_rows(q, k_vals, bt, mask, policy) -> np.ndarray:
    """(B, H, rows) bool: LUT rows holding a logit within ``EDGE_LOGIT``
    of a bin's rounding edge, from float64 logits of the same f32 inputs.
    ``mask`` (B, 1, rows, Lk) marks the keys each row sees."""
    from repro.kernels.lut_attention import ops

    if policy.impl == "exact":
        return np.zeros(q.shape[:3], bool)
    step = ops._tables_for(policy).exp_step if policy.impl == "lut2d" else 1.0
    kv = np.asarray(k_vals, np.float64)[np.asarray(bt)]  # (B, MP, KVH, PS, Dh)
    kv = kv.transpose(0, 2, 1, 3, 4).reshape(B, KVH, MP * PS, DH)
    qg = np.asarray(q, np.float64).reshape(B, KVH, H // KVH, -1, DH)
    s = np.einsum("bngrd,bnkd->bngrk", qg, kv).reshape(B, H, -1, MP * PS)
    s = s * DH ** -0.5
    d = (np.where(mask, s, -np.inf).max(-1, keepdims=True) - s) / step
    # jnp.round bins: edges at half-integers (floor bins: at integers)
    off = 0.5 if policy.index_mode == "round" else 0.0
    dist = np.abs((d + off) - np.round(d + off)) * step
    return ((dist < EDGE_LOGIT) & mask).any(-1)


def _parity_case(prob, phase: str, policy, pool: str):
    """The compiled kernel vs the dense reference (run under ``highest``
    matmul precision): max-abs error over the rows held to the limit,
    the limit, and the rows excused for a logit at a bin edge (count,
    rows compared, their max-abs error)."""
    from repro.core.quantization import dequantize_rows
    from repro.kernels.lut_attention import ops

    k, v, scales = prob[pool]
    bt = prob["bt"]
    keys = np.arange(MP * PS)[None, None, None, :]
    if phase == "decode":
        q = prob["q_decode"]
        lens = jnp.asarray(DECODE_LENS, jnp.int32)
        assert ops.resolve_paged_backend("pallas") == "pallas"

        def attend(backend):
            return ops.lut_attention_paged_decode(
                q, k, v, bt, lens, policy, backend=backend, **scales)
        rows = np.ones((B, 1), bool)
        mask = keys < np.asarray(DECODE_LENS)[:, None, None, None]
    else:
        q = prob["q_prefill"]
        start = jnp.asarray(PREFILL_START, jnp.int32)
        lens = start + jnp.asarray(PREFILL_CHUNK, jnp.int32)
        assert ops.resolve_paged_prefill_backend("pallas") == "pallas"

        def attend(backend):
            return ops.lut_attention_paged_prefill(
                q, k, v, bt, start, lens, policy, backend=backend, **scales)
        # structural padding rows past a slot's chunk are discarded
        rows = np.arange(CHUNK)[None, :] < np.asarray(PREFILL_CHUNK)[:, None]
        qpos = (np.asarray(PREFILL_START)[:, None, None, None]
                + np.arange(CHUNK)[None, None, :, None])
        mask = (keys < np.asarray(lens)[:, None, None, None]) & (keys <= qpos)

    got = jax.jit(lambda: attend("pallas"))()
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda: attend("dense"))()
    if scales:
        k_vals = dequantize_rows(k, scales["k_scales"].reshape(k.shape[:3]))
        v_vals = dequantize_rows(v, scales["v_scales"].reshape(v.shape[:3]))
    else:
        k_vals, v_vals = k, v
    held = np.broadcast_to(rows[:, None, :], (B, H, rows.shape[1]))
    edge = _edge_rows(q, k_vals, bt, mask, policy) & held
    err = np.abs(np.asarray(got) - np.asarray(ref)).max(-1)   # (B, H, rows)
    tol = PARITY_TOL * float(jnp.max(jnp.abs(v_vals)))
    far = float(np.max(err, initial=0.0, where=held & ~edge))
    near = float(np.max(err, initial=0.0, where=edge))
    return far, tol, int(edge.sum()), int(held.sum()), near


def phase_parity() -> None:
    from repro.core.policies import SoftmaxPolicy

    policies = {"exact": SoftmaxPolicy(),
                "rexp/uint8": SoftmaxPolicy(impl="rexp", precision="uint8"),
                "lut2d/uint8": SoftmaxPolicy(impl="lut2d",
                                             precision="uint8")}
    prob = _parity_problem()
    failed = []
    for phase in ("decode", "prefill"):
        for name, pol in policies.items():
            for pool in ("f32", "int8"):
                err, tol, n_edge, n_rows, err_edge = _parity_case(
                    prob, phase, pol, pool)
                ok = err <= tol
                print(f"parity: {phase:7s} {name:11s} {pool:4s} pool  "
                      f"max-abs {err:.3e}  tol {tol:.3e}  "
                      f"{'ok' if ok else 'FAIL'}; {n_edge}/{n_rows} rows "
                      f"with a logit at a bin edge, max-abs {err_edge:.3e}",
                      flush=True)
                if not ok:
                    failed.append((phase, name, pool))
    if failed:
        sys.exit(f"parity: {len(failed)} case(s) outside tolerance: "
                 f"{failed}")


# ---------------------------------------------------------------------------
# Phase: serve
# ---------------------------------------------------------------------------


def _expect_empty_device(label: str) -> None:
    gc.collect()
    live = sum(a.nbytes for a in jax.live_arrays())
    if live > MAX_LIVE_BYTES_BETWEEN_PHASES:
        sys.exit(f"{label}: {live} bytes still live on the device from "
                 f"an earlier phase")


def _serve(label: str, argv: list[str]) -> dict:
    from repro.launch import serve

    _expect_empty_device(label)
    t0 = time.time()
    summary = serve.main(argv)
    print(f"{label}: wall {time.time() - t0:.1f} s (compile included)",
          flush=True)
    new_tokens = int(argv[argv.index("--new-tokens") + 1])
    n_requests = int(argv[argv.index("--batch") + 1])
    results = summary["results"]
    if len(results) != n_requests:
        sys.exit(f"{label}: {len(results)} of {n_requests} requests "
                 f"finished")
    for rid, res in results.items():
        n = summary.get("streamed", {}).get(rid, len(res.tokens))
        if not n == len(res.tokens) == new_tokens:
            sys.exit(f"{label}: request {rid} streamed {n} tokens, result "
                     f"holds {len(res.tokens)}, want {new_tokens}")
    return summary


def phase_serve(softmax: str) -> int:
    label = f"serve[{softmax}]"
    summary = _serve(label, SERVE_ARGS + ["--paged-backend", "pallas",
                                          "--engine", "pipelined", "--serve"]
                     + SOFTMAX_ARGS[softmax])
    if summary["backends"] != ("pallas", "pallas"):
        sys.exit(f"{label}: paged attention resolved to "
                 f"{summary['backends']}, not the compiled kernels")
    print(f"{label}: {len(summary['results'])} requests streamed through "
          f"the compiled Pallas paged kernels (decode, prefill); peak "
          f"device bytes in use so far {summary['peak_bytes']}", flush=True)
    return summary["peak_bytes"]


def _serve_recorded(label: str, argv: list[str]):
    """``_serve`` with every logits row the engine samples from recorded,
    per request (the continuous engine samples on the host)."""
    from unittest import mock

    from repro.runtime import ServingEngine

    sample = ServingEngine._sample
    logits: dict[int, list[np.ndarray]] = {}

    def recording(self, seq, row):
        logits.setdefault(seq.request.id, []).append(
            np.array(row, np.float32))
        return sample(self, seq, row)

    with mock.patch.object(ServingEngine, "_sample", recording):
        summary = _serve(label, argv + ["--engine", "continuous"])
    return summary, logits


def _compare_logits(label: str, ref, got) -> None:
    """Hold ``got``'s logits to ``ref``'s, both ``_serve_recorded``
    results of the same requests: every step at which the two token
    streams still share their prefix, up to and including the step where
    they part.  Logits within ``LOGIT_TOL`` of the span can only pick
    different tokens at a near-tie (a top-2 gap under 2·tol), which is
    printed for the step where they part."""
    (s_ref, l_ref), (s_got, l_got) = ref, got
    worst = 0.0
    for rid in sorted(l_ref):
        t_ref = s_ref["results"][rid].tokens
        t_got = s_got["results"][rid].tokens
        n_same = int(np.cumprod(t_ref == t_got).sum())
        rels = [float(np.max(np.abs(g - r))) / float(r.max() - r.min())
                for r, g in zip(l_ref[rid][:n_same + 1],
                                l_got[rid][:n_same + 1])]
        worst = max(worst, *rels)
        line = (f"{label}: request {rid} logits max-abs/span: first token "
                f"{rels[0]:.3e}, max over {len(rels)} step(s) "
                f"{max(rels):.3e} (tol {LOGIT_TOL}); {n_same}/{len(t_ref)} "
                f"tokens equal")
        if n_same < len(t_ref):
            r = np.sort(l_ref[rid][n_same])
            line += (f"; at step {n_same} the top-2 gap is "
                     f"{float(r[-1] - r[-2]) / float(r[-1] - r[0]):.3e} of "
                     f"the span")
        print(line, flush=True)
    if worst > LOGIT_TOL:
        sys.exit(f"{label}: logits differ by {worst:.3e} of their span "
                 f"(tol {LOGIT_TOL})")


def phase_backends() -> None:
    """The same rexp/uint8 requests through the compiled kernels and
    through the dense reference (``--paged-backend dense``)."""
    args = SERVE_ARGS + SOFTMAX_ARGS["rexp"]
    got = _serve_recorded("backends[pallas]",
                          args + ["--paged-backend", "pallas"])
    if got[0]["backends"] != ("pallas", "pallas"):
        sys.exit(f"backends: resolved to {got[0]['backends']}")
    ref = _serve_recorded("backends[dense]",
                          args + ["--paged-backend", "dense"])
    _compare_logits("backends", ref, got)


def phase_tensor_parallel() -> None:
    """The same requests at tp=4 and at tp=1 through the dense reference
    (which the tp=4 'heads' regime runs per head group): sharding is the
    only difference."""
    args = SERVE_ARGS + SOFTMAX_ARGS["rexp"]
    got = _serve_recorded("serve[tp=4]",
                          args + ["--tp", "4", "--shard-params"])
    s4 = got[0]
    if s4["regime"] != "heads":
        sys.exit(f"tp: expected the 'heads' regime, got {s4['regime']!r}")
    per_dev = s4["pool_bytes_per_device"]
    print(f"tp: pool bytes per device {per_dev}", flush=True)
    if len(per_dev) != 4 or len(set(per_dev.values())) != 1:
        sys.exit(f"tp: the pool is not split evenly over 4 devices: "
                 f"{per_dev}")
    ref = _serve_recorded("serve[tp=1]", args + ["--paged-backend", "dense"])
    _compare_logits("tp", ref, got)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tensor-parallel serve comparison "
                         "(--tp 4 vs --tp 1) on four chips")
    args = ap.parse_args()
    compile_s = _compile_seconds()
    t0 = time.time()
    device = phase_device(4 if args.four_chips else 1)
    if args.four_chips:
        phase_tensor_parallel()
    else:
        phase_parity()
        phase_serve("rexp")
        peak = phase_serve("exact")
        print(f"serve: peak device bytes in use {peak}", flush=True)
        phase_backends()
    print(f"compile: {compile_s[1]} XLA programs, {compile_s[0]:.1f} s; "
          f"total wall {time.time() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
