"""Pallas TPU kernels for the paper's compute hot-spot (softmax inside
attention) plus the exact-softmax baseline:

  lut_softmax/     row-wise LUT softmax (REXP + 2D-LUT)
  lut_attention/   fused flash-style attention with LUT softmax, and the
                   paged kernels (``paged_decode.py`` for single-token
                   decode, ``paged_prefill.py`` for prompt chunks) that
                   serve the continuous-batching engine straight off the
                   head-major KV pool ``(n_pages, KVH, page_size, Dh)``
                   via scalar-prefetched block tables — no contiguous
                   per-slot KV gather on the kernel path, either phase
  flash_attention/ exact online-softmax flash attention

Each package ships <name>.py (pl.pallas_call + BlockSpec), ops.py (the
jit'd public wrapper with XLA fallback paths) and ref.py (pure-jnp
oracle).  Kernels are validated in interpret mode on CPU, and the paged
kernels are compiled ahead of time for a TPU v5e at qwen3-32b widths by
``tests/test_tpu_compile.py``.

Paged-attention dispatch (``ops.lut_attention_paged_decode`` and
``ops.lut_attention_paged_prefill`` — ONE matrix covers both; the
canonical statement lives in ``lut_attention/ops.py`` and a test pins
the docs to it): ``auto`` runs the Pallas kernel on TPU and the dense
gather-from-block-table reference elsewhere, GPU included (the
scalar-prefetch grid spec is Mosaic/TPU-only, so GPU falls back to
dense until a Mosaic-GPU port lands); ``pallas`` forces the kernel —
compiled on TPU, interpret mode off-TPU (the CI parity configuration,
never a silent stand-in); ``dense`` forces the reference.  A ``mesh``
whose 'model' axis has tp > 1 overrides the knob with the
tensor-parallel rows (``lut_attention/sharded_paged.py``): the 'heads'
regime (KVH % tp == 0) runs each head group locally off a
KV-head-sharded pool with no attention collectives, and the 'pages'
regime shards the pool's physical-page axis and reduces only (B, H, 1)
pmax/psum partials — never gathered KV.  With ``kv_dtype=int8`` every
row reads int8 pages plus f32 per-token × KV-head scales (quantized
rows of the same matrix): the fused kernels stream scale blocks beside
their pages and dequantize in VMEM, the dense/mesh paths dequantize the
gathered view, and under a mesh the scales shard with their pages in
both regimes.  All paths share one integer LUT pipeline and produce the
same tokens.
"""
