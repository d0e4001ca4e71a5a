"""Serving launcher: batched generation with the LUT softmax active.

Loads a checkpoint (or random-inits), then serves a batch of prompts
with the selected softmax policy — the production path for the paper's
technique.  Two drivers:

* ``--engine lockstep``    — fixed-batch ``serve_loop.generate`` (every
  request shares one prompt length and finishes together);
* ``--engine continuous``  — the paged-KV continuous-batching engine
  (mixed prompt/output lengths share the decode batch; default);
* ``--engine pipelined``   — the continuous engine with on-device
  sampling and one-step-ahead dispatch (host scheduling overlaps
  device compute; ``--pipeline-depth`` bounds the in-flight steps).

``--serve`` switches from batch driving to the asyncio front-end
(``runtime/server.py``): requests are submitted concurrently and
consumed token by token through streaming handles, with admission
control via ``--max-queue`` / ``--backpressure``, then the server shuts
down cleanly.  Tokens are identical to the batch path either way.

``--prefill-chunk`` sizes the continuous engine's chunked paged
prefill: prompts enter the page pool in fixed-size chunks (one compile
for every prompt length) interleaved with decode steps, so a long
prompt does not stall running slots.

``--paged-backend`` selects the continuous engine's paged-attention
kernels for BOTH phases (decode steps and prefill chunks): ``auto``
(default) runs the fused Pallas paged kernels on TPU and the dense
block-table references elsewhere (GPU included, until a Mosaic-GPU
port lands); ``pallas`` forces the kernels (interpret mode off-TPU —
slow, for validation, never a silent stand-in); ``dense`` forces the
references everywhere.  Output tokens are identical across backends.

``--scale-down d_model,heads,vocab`` shrinks the widths for CPU runs;
``--scale-down none`` (or an empty value) keeps the published widths
and cuts only depth, to ``--periods`` layer periods — the form that runs
on a TPU.  Weights are random (``--seed``) unless ``--ckpt-dir`` holds a
checkpoint; they are made by one jitted ``model.init`` directly in their
final placement.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-32b \
      --scale-down 256,8,512 --softmax rexp --precision uint8 \
      --batch 4 --prompt-len 64 --new-tokens 32 --engine continuous

:func:`main` takes the argument list and returns a summary (results,
streamed token counts, the resolved attention paths, peak device bytes)
so a caller in the same process can check the run.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, RunConfig, get_arch
from repro.checkpoint.manager import CheckpointManager
from repro.core.policies import SoftmaxPolicy
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.runtime import EngineConfig, PagedCacheConfig, ServingEngine
from repro.runtime.serve_loop import generate


def _init_params(model, key, mesh=None, shard_params: bool = False):
    """Random weights from one jitted ``model.init``, made on the device
    in their final placement: no optimizer state, no host copy, and on a
    mesh no full replica on the first device before the engine places
    them (TP-sharded with ``shard_params``, else replicated)."""
    out = None
    if mesh is not None:
        from repro.runtime.partitioning import serving_param_shardings
        out = serving_param_shardings(jax.eval_shape(model.init, key), mesh,
                                      shard_params)
    return jax.jit(model.init, out_shardings=out)(key)


def _pool_bytes_per_device(pools) -> dict[int, int]:
    """Bytes of the paged KV pools held by each device (shards summed)."""
    out: dict[int, int] = {}
    for leaf in jax.tree_util.tree_leaves(pools):
        for sh in leaf.addressable_shards:
            out[sh.device.id] = out.get(sh.device.id, 0) + sh.data.nbytes
    return out


def main(argv: list[str] | None = None) -> dict:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), required=True)
    ap.add_argument("--scale-down", default="256,8,512",
                    help="d_model,n_heads,vocab of a reduced same-family "
                         "config; 'none' (or empty) keeps the published "
                         "widths and cuts only depth to --periods")
    ap.add_argument("--periods", type=int, default=2)
    ap.add_argument("--softmax", default="rexp",
                    choices=["exact", "rexp", "lut2d"])
    ap.add_argument("--precision", default="uint8",
                    choices=["int16", "uint8", "uint4", "uint2"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--engine", default="continuous",
                    choices=["lockstep", "continuous", "pipelined"])
    ap.add_argument("--pipeline-depth", type=int, default=2,
                    help="pipelined engine: max device steps in flight "
                         "before the host blocks on a harvest (2 = "
                         "double buffering)")
    ap.add_argument("--serve", action="store_true",
                    help="drive through the asyncio streaming front-end "
                         "instead of the batch path")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="--serve: admission bound on requests waiting "
                         "for a slot (default: unbounded)")
    ap.add_argument("--backpressure", default="reject",
                    choices=["reject", "wait"],
                    help="--serve: at --max-queue, reject new requests "
                         "(ServerSaturatedError) or make submitters wait")
    ap.add_argument("--paged-backend", default="auto",
                    choices=["auto", "pallas", "dense"],
                    help="continuous-engine paged attention (decode AND "
                         "prefill chunks): fused Pallas paged kernels vs "
                         "dense block-table references")
    ap.add_argument("--kv-dtype", default="f32", choices=["f32", "int8"],
                    help="KV page-pool storage: int8 stores pages "
                         "quantized with per-token f32 scales (halved "
                         "pool bytes and streamed VMEM; dequant inside "
                         "the paged kernels; <1%% accuracy budget — see "
                         "README §Quantized KV pool)")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--n-pages", type=int, default=256)
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="prompt tokens per chunked-prefill step; one "
                         "compile serves every prompt length, and chunks "
                         "interleave with decode so long prompts do not "
                         "stall running slots")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="prompt tokens prefilled per engine step "
                         "(default: one chunk)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share full-page prompt prefixes across requests "
                         "(refcounted pages + copy-on-write): matched "
                         "prefixes skip prefill entirely; tokens stay "
                         "identical to no-sharing")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree for the continuous "
                         "engine: shard the page pool (and, with "
                         "--shard-params, the weights) over a "
                         "('data'=1, 'model'=tp) mesh of the host's "
                         "devices; output stays token-identical to "
                         "--tp 1 (bitwise attention in the 'heads' "
                         "regime; argmax-level in 'pages', where the "
                         "final f32 contraction reassociates — see "
                         "README)")
    ap.add_argument("--shard-params", action="store_true",
                    help="with --tp > 1: TP-shard the weights instead "
                         "of replicating them (production layout; "
                         "matmul reductions may reassociate at "
                         "roundoff level)")
    args = ap.parse_args(argv)
    if args.shard_params and args.tp <= 1:
        ap.error("--shard-params requires --tp > 1 (there is no mesh to "
                 "shard the weights over)")

    arch = get_arch(args.arch)
    if args.scale_down and args.scale_down.lower() != "none":
        d, h, v = (int(x) for x in args.scale_down.split(","))
        arch = arch.scaled_down(d_model=d, n_heads=h, vocab=v,
                                n_periods=args.periods)
    else:
        arch = arch.with_layers(args.periods)
    model = build_model(arch)

    policy = (SoftmaxPolicy(impl=args.softmax, precision=args.precision)
              if args.softmax != "exact" else SoftmaxPolicy())
    run = RunConfig(dtype="float32", attention_backend="naive",
                    scan_layers=True, softmax_policy=policy, ssm_chunk=32,
                    paged_backend=args.paged_backend,
                    kv_dtype=args.kv_dtype)

    engine_ok = (not arch.encoder_layers
                 and all(s.mixer == "attn" for s in arch.period))
    use_engine = args.engine in ("continuous", "pipelined") and engine_ok
    if args.engine in ("continuous", "pipelined") and not engine_ok:
        print("continuous engine serves attention-only decoder LMs; "
              "falling back to lockstep")
    if args.serve and not use_engine:
        ap.error("--serve requires the continuous or pipelined engine "
                 "(the lockstep path has no scheduler to stream from)")
    if args.tp > 1 and not use_engine:
        # never report single-device lockstep numbers as a --tp run
        ap.error("--tp > 1 requires the continuous engine (attention-only "
                 "decoder LM with --engine continuous)")
    if args.prefix_cache and not use_engine:
        ap.error("--prefix-cache requires the continuous engine (the "
                 "lockstep path has no page pool to share)")

    mesh = None
    if args.tp > 1:
        from repro.launch.mesh import make_serving_mesh
        from repro.kernels.lut_attention.ops import paged_mesh_regime
        mesh = make_serving_mesh(args.tp)
        print(f"tensor-parallel tp={args.tp}: "
              f"{paged_mesh_regime(mesh, arch.n_kv_heads)!r} regime "
              f"(KVH={arch.n_kv_heads})")

    key = jax.random.PRNGKey(args.seed)
    params = _init_params(model, key, mesh, args.shard_params)
    if args.ckpt_dir:
        from repro.runtime.train_loop import TrainState
        mgr = CheckpointManager(args.ckpt_dir)
        # restore params only (opt=None subtree has no leaves to match)
        restored = mgr.restore_latest(TrainState(params=params, opt=None,
                                                 ef=None))
        if restored:
            params = restored[0].params
            print(f"restored step {restored[1]}")

    if use_engine:
        import numpy as np
        page_size = args.page_size
        max_total = args.prompt_len + args.new_tokens
        mp = -(-max_total // page_size)
        cache = PagedCacheConfig(n_pages=args.n_pages, page_size=page_size,
                                 max_pages_per_seq=mp)
        from repro.runtime import PipelinedEngine
        engine_cls = (PipelinedEngine if args.engine == "pipelined"
                      else ServingEngine)
        eng = engine_cls(model, params, run, EngineConfig(
            n_slots=args.batch, cache=cache,
            prefill_chunk=args.prefill_chunk,
            prefill_budget=args.prefill_budget,
            prefix_cache=args.prefix_cache,
            pipeline_depth=args.pipeline_depth,
            mesh=mesh, shard_params=args.shard_params))
        if args.kv_dtype != "f32":
            pool0 = eng.pools[0]
            page_bytes = sum(int(v.nbytes)
                             for k, v in pool0.items() if "pages" in k)
            scale_bytes = sum(int(v.nbytes)
                              for k, v in pool0.items() if "scales" in k)
            f32_bytes = 4 * page_bytes  # int8 pages, same element count
            print(f"kv_dtype={args.kv_dtype}: quantized KV pool — "
                  f"{page_bytes + scale_bytes} pool bytes/layer "
                  f"(pages {page_bytes} + scales {scale_bytes}) vs "
                  f"{f32_bytes} at f32, "
                  f"{(page_bytes + scale_bytes) / f32_bytes:.2f}x")
        per_dev = None
        if mesh is not None:
            per_dev = _pool_bytes_per_device(eng.pools)
            print("pool bytes per device: " + ", ".join(
                f"{d}={n}" for d, n in sorted(per_dev.items())))
        from repro.kernels.lut_attention.ops import (
            paged_mesh_regime, resolve_paged_backend,
            resolve_paged_prefill_backend)
        regime = paged_mesh_regime(mesh, arch.n_kv_heads)
        backends = (resolve_paged_backend(args.paged_backend),
                    resolve_paged_prefill_backend(args.paged_backend))
        if regime is not None:  # the mesh rows override the backend knob
            attn = (f"sharded '{regime}' regime, tp={args.tp}, both phases")
        else:
            attn = (f"decode attention: {backends[0]}; prefill "
                    f"attention: {backends[1]}")
        summary = dict(regime=regime, backends=None if regime else backends,
                       pool_bytes_per_device=per_dev)
        rng = np.random.default_rng(args.seed)
        if args.serve:
            import asyncio
            from repro.runtime import AsyncServingServer
            streamed: dict[int, int] = {}

            async def serve_demo():
                async with AsyncServingServer(
                        eng, max_queue=args.max_queue,
                        backpressure=args.backpressure) as srv:

                    async def one(i: int):
                        plen = max(1, int(rng.integers(
                            args.prompt_len // 2, args.prompt_len + 1)))
                        prompt = rng.integers(0, arch.vocab_size, size=plen)
                        stream = await srv.submit(
                            prompt, args.new_tokens,
                            temperature=args.temperature,
                            seed=args.seed + i)
                        n = 0
                        async for _tok in stream:
                            n += 1
                        res = await stream.result()
                        streamed[res.request_id] = n
                        print(f"request {res.request_id}: streamed {n} "
                              f"tokens (ttft {res.ttft_s:.3f}s, "
                              f"finish={res.finish_reason})")
                        return res

                    t0 = time.time()
                    results = await asyncio.gather(
                        *[one(i) for i in range(args.batch)])
                    dt = time.time() - t0
                    toks = sum(len(r.tokens) for r in results)
                    print(f"policy={policy.impl}/{policy.precision} "
                          f"streaming [{engine_cls.__name__}; {attn}]: "
                          f"{toks} tokens in {dt:.2f}s "
                          f"({toks/dt:.1f} tok/s incl. compile)")
                print("server: clean shutdown")
                return {r.request_id: r for r in results}

            results = asyncio.run(serve_demo())
            summary.update(results=results, streamed=streamed)
        else:
            t0 = time.time()
            results = _drive_batch(args, arch, eng, cache, rng)
            dt = time.time() - t0
            toks = eng.stats.tokens
            ttfts = [r.ttft_s for r in results.values()
                     if r.ttft_s is not None]
            print(f"policy={policy.impl}/{policy.precision} "
                  f"continuous-batching [{attn}]: "
                  f"{toks} tokens in {dt:.2f}s ({toks/dt:.1f} tok/s incl. "
                  f"compile; {eng.stats.steps} decode steps, "
                  f"{eng.stats.prefill_steps} prefill chunks of "
                  f"{args.prefill_chunk}, {eng.stats.preemptions} "
                  f"preemptions, mean TTFT {np.mean(ttfts):.3f}s, max "
                  f"decode stall {eng.stats.max_decode_gap_s:.3f}s)")
            if args.prefix_cache:
                print(f"prefix cache: {eng.stats.prefix_hit_tokens} prompt "
                      f"tokens served from shared pages "
                      f"({eng.stats.prompt_tokens} prefilled), "
                      f"{eng.stats.pages_shared} pages shared, "
                      f"{eng.stats.cow_copies} copy-on-write copies")
            print("sample token ids:", results[0].tokens[:16].tolist())
            summary.update(results=results)
        summary.update(peak_bytes=_report_peak())
        return summary

    prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                arch.vocab_size)
    enc = (jax.random.normal(key, (args.batch, arch.encoder_seq,
                                   arch.d_model), jnp.float32)
           if arch.encoder_layers else None)

    t0 = time.time()
    out = generate(model, params, prompt, run,
                   max_new_tokens=args.new_tokens, encoder_input=enc,
                   temperature=args.temperature, seed=args.seed)
    dt = time.time() - t0
    toks = args.batch * args.new_tokens
    print(f"policy={policy.impl}/{policy.precision} generated {toks} tokens "
          f"in {dt:.2f}s ({toks/dt:.1f} tok/s incl. compile)")
    print("sample token ids:", out[0, :16].tolist())
    return dict(tokens=out, peak_bytes=_report_peak())


def _report_peak() -> int | None:
    """Print and return the peak bytes in use on the first device, where
    the backend reports it (TPU does; the CPU backend has no statistics)."""
    stats = jax.devices()[0].memory_stats()
    peak = None if stats is None else int(stats["peak_bytes_in_use"])
    print(f"peak device bytes in use: "
          f"{'not reported by this backend' if peak is None else peak}")
    return peak


def _drive_batch(args, arch, eng, cache, rng) -> dict:
    """Batch driving: mixed prompt lengths, the workload lockstep cannot
    batch.  With the prefix cache on, every request shares a common
    preamble (the system-prompt pattern the cache exists for) and the
    batch runs as TWO waves: the first writes the preamble pages, the
    second — arriving after those pages are published — maps them in
    with zero prefill work (a single simultaneous wave all admits before
    anything is published, so nothing would ever hit)."""
    import numpy as np
    preamble = rng.integers(0, arch.vocab_size, size=args.prompt_len // 2)
    handles = []

    def add_wave(n, wave):
        for b in range(n):
            plen = max(1, int(rng.integers(args.prompt_len // 2,
                                           args.prompt_len + 1)))
            tail = rng.integers(0, arch.vocab_size, size=plen)
            prompt = (np.concatenate([preamble, tail])
                      [:cache.max_context - args.new_tokens]
                      if args.prefix_cache else tail)
            handles.append(eng.add_request(
                prompt, args.new_tokens, temperature=args.temperature,
                seed=args.seed + wave * args.batch + b))

    add_wave(args.batch, wave=0)
    if args.prefix_cache:
        for h in handles:
            h.result()
        add_wave(args.batch, wave=1)
    return {int(h): h.result() for h in handles}


if __name__ == "__main__":
    main()
