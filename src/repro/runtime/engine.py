"""Continuous-batching serving engine over the paged KV cache.

Replaces the lockstep ``serve_loop.generate`` path for mixed workloads:
requests of *different* prompt lengths and output budgets share one
fixed-capacity decode batch.  Each step, finished sequences leave,
queued requests join, and every slot decodes against its own block-table
view of the shared page pool — no re-jitting anywhere, because every
device program's shapes are fixed at engine construction.

Prefill is **chunked and paged** (Sarathi-style): an admitted prompt is
walked in fixed-size chunks whose K/V are written straight into the
page pool through the sequence's block table — no contiguous
``(1, max_context)`` cache is ever written, no scatter-after-the-fact
(the chunk attention's reference path reads a transient block-table
view per chunk, like the dense decode reference), and because
the chunk program's shapes are ``(1, prefill_chunk)`` regardless of
prompt length, ONE prefill compile serves every request (the old path
retraced per distinct length).  A per-step token budget interleaves
prefill chunks with decode steps, so a long prompt no longer
head-of-line-stalls the running slots; time-to-first-token for the
prompt trades off against decode smoothness via ``prefill_budget``.

The attention softmax is governed by ``run.softmax_policy`` exactly as
in the lockstep path (exact / REXP / 2D-LUT at any precision).  BOTH
phases ship the block tables straight to the paged-attention dispatch
(``run.paged_backend``): decode through
``lut_attention_paged_decode`` and chunk prefill through
``lut_attention_paged_prefill`` — on TPU the fused Pallas kernels
stream K/V pages directly from the pool (no contiguous gather on
either phase), while CPU/GPU hosts run the dense block-table
references — identical per-key numerics either way.

Greedy decoding is bit-faithful to ``generate()``: chunked prefill
masks exactly the keys the whole-prompt path masks (per-chunk
max-normalization over the same visible set keeps the LUT numerators /
denominators in their calibrated ranges), and the paged decode masks
exactly the keys the contiguous path masks.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
import warnings
from collections import deque
from typing import Callable, Sequence as SeqOf

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import RunConfig
from repro.kernels.lut_attention.paged_decode import decode_block_pages
from repro.models.model_zoo import Model
from repro.models import transformer as TF
from repro.runtime.paged_cache import (PagedCacheConfig, decode_view,
                                       prefill_chunk_view, view_arrays)
from repro.runtime.scheduler import (PENDING_TOKEN, Request, Scheduler,
                                     SeqState, Sequence)
from repro.runtime.serve_loop import sample_tokens


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Everything that shapes a :class:`ServingEngine`'s programs.

    One object instead of seven loose keyword arguments: construction
    sites name exactly the knobs they change, defaults live in one
    place, and a config can be stored / logged / passed through
    launchers without re-spelling the signature.  (The old
    ``ServingEngine(..., n_slots=, cache=, ...)`` kwargs still work for
    one release behind a ``DeprecationWarning``.)
    """

    n_slots: int = 4                 # decode-batch capacity
    cache: PagedCacheConfig = PagedCacheConfig()
    prefill_chunk: int = 16          # prompt tokens per chunk program
    prefill_budget: int | None = None  # tokens per step (None → chunk)
    prefix_cache: bool = False       # copy-on-write prompt-prefix sharing
    #: KV page-pool storage: None inherits ``RunConfig.kv_dtype``;
    #: 'f32' / 'int8' override it for this engine (int8 = per-token ×
    #: KV-head f32 scales, dequantized inside the paged kernels).
    kv_dtype: str | None = None
    jit: bool = True
    mesh: object = None              # jax.sharding.Mesh | None
    shard_params: bool = False
    #: :class:`PipelinedEngine` only — max device steps in flight before
    #: the host blocks on a harvest.  2 = classic double buffering (plan
    #: step N+1 while step N computes); 1 degenerates to the synchronous
    #: cadence (dispatch, harvest, dispatch, ...) but still samples
    #: on-device.
    pipeline_depth: int = 2


class RequestHandle:
    """Ticket for one queued request.

    What :meth:`ServingEngine.add_request` returns: carries the request
    id plus live accessors — ``done``, ``result()`` (drives the engine
    until this request finishes), ``ttft_s`` and ``prefix_hit_tokens``.
    Hashes/compares/sorts as its integer id, so existing code that
    collected bare ids (dict keys, ``sorted(...)``, ``int(...)``)
    keeps working unchanged.
    """

    __slots__ = ("id", "_engine")

    def __init__(self, rid: int, engine: "ServingEngine"):
        self.id = rid
        self._engine = engine

    @property
    def done(self) -> bool:
        return self.id in self._engine._results

    def result(self) -> "GenerationResult":
        """Drive the engine until this request finishes; its result."""
        while not self.done:
            if not self._engine.has_work():
                raise RuntimeError(
                    f"request {self.id} cannot finish: engine has no work")
            self._engine.step()
        return self._engine._results[self.id]

    @property
    def ttft_s(self) -> float | None:
        """Enqueue → first token, seconds (None until sampled)."""
        res = self._engine._results.get(self.id)
        if res is not None:
            return res.ttft_s
        return self._engine._ttft.get(self.id)

    @property
    def prefix_hit_tokens(self) -> int:
        """Prompt tokens this request served from shared pages."""
        res = self._engine._results.get(self.id)
        if res is not None:
            return res.prefix_hit_tokens
        seq = self._engine._seqs.get(self.id)
        return seq.prefix_hit_tokens if seq is not None else 0

    def __int__(self) -> int:
        return self.id

    __index__ = __int__

    def __hash__(self) -> int:
        return hash(self.id)

    def __eq__(self, other) -> bool:
        if isinstance(other, RequestHandle):
            return self.id == other.id
        if isinstance(other, int):
            return self.id == other
        return NotImplemented

    def __lt__(self, other) -> bool:
        if isinstance(other, (RequestHandle, int)):
            return self.id < int(other)
        return NotImplemented

    def __repr__(self) -> str:
        return f"RequestHandle(id={self.id}, done={self.done})"


@dataclasses.dataclass
class GenerationResult:
    request_id: int
    tokens: np.ndarray           # (n_generated,) int32
    finish_reason: str           # 'length' | 'eos'
    n_evictions: int
    ttft_s: float | None = None  # enqueue → first token, seconds
    prefix_hit_tokens: int = 0   # prompt tokens served from shared pages


@dataclasses.dataclass
class EngineStats:
    steps: int = 0               # decode steps executed
    prefill_steps: int = 0       # prefill-chunk steps (counted separately)
    prefills: int = 0            # prompts fully prefilled
    decode_tokens: int = 0       # useful tokens produced by decode steps
    # first tokens, sampled from the final prefill chunk's logits — one
    # per completed prefill, NOT prompt tokens (that's ``prompt_tokens``;
    # this field was misleadingly named ``prefill_tokens`` before)
    first_tokens: int = 0
    prompt_tokens: int = 0       # prompt tokens pushed through chunks
    preemptions: int = 0
    # prefix-cache counters (all zero with the cache off), named for
    # what they count, per the first_tokens precedent:
    prefix_hit_tokens: int = 0   # prompt tokens never re-prefilled
    pages_shared: int = 0        # trie pages mapped into block tables
    cow_copies: int = 0          # copy-on-write page duplications
    # longest gap between consecutive decode-step COMPLETIONS
    # (the worst inter-token wait a running slot observes; includes
    # whatever prefill work ran in between)
    max_decode_gap_s: float = 0.0
    wall_s: float = 0.0
    # pipelined-engine counters (all zero on the synchronous engine):
    speculative_wasted: int = 0  # dispatched slot-steps rolled back at EOS
    inflight_peak: int = 0       # max device steps simultaneously in flight
    queue_depth_peak: int = 0    # max waiting-queue depth observed
    # the decode kernel's block sweep (``decode_block_pages`` pages a
    # block): the blocks its grid walks, slots × blocks per decode step,
    # and those holding a key, Σ over slots of ceil(kv_len / block keys);
    # the rest the kernel skips
    decode_kv_blocks: int = 0
    decode_kv_blocks_live: int = 0

    @property
    def tokens(self) -> int:
        """Generated (sampled) tokens: decode steps + first tokens."""
        return self.decode_tokens + self.first_tokens


#: legacy ``ServingEngine(**kwargs)`` names accepted (deprecated) in
#: place of an :class:`EngineConfig` — exactly the old signature.
_LEGACY_ENGINE_KWARGS = frozenset(
    f.name for f in dataclasses.fields(EngineConfig)) - {"prefix_cache",
                                                         "pipeline_depth",
                                                         "kv_dtype"}


class ServingEngine:
    """Fixed-capacity continuous-batching driver.

    Args:
      model/params/run: as for ``serve_loop.generate``; the arch must be
        a decoder-only, attention-mixer LM (the serving targets).
      config: an :class:`EngineConfig`.  Its knobs:

      * ``n_slots``: decode-batch capacity (sequences decoding
        concurrently).
      * ``cache``: page-pool sizing; ``cache.max_context`` bounds
        ``prompt + max_new_tokens`` of any request.
      * ``prefill_chunk``: prompt tokens per prefill-chunk program.
        Shapes are fixed by this, so one compile serves every prompt
        length.
      * ``prefill_budget``: prompt tokens prefilled per engine step
        (default: one chunk).  Smaller → smoother decode, later first
        tokens; larger → the reverse.  At least one chunk always runs
        per step.
      * ``prefix_cache``: share full-page prompt prefixes across
        requests via a refcounted radix trie with copy-on-write (see
        ``runtime/prefix_cache.py``).  Matched prefixes skip prefill
        entirely; output stays token-identical to the no-sharing
        engine.
      * ``jit``: wrap the chunk/decode steps in jax.jit.  Both compile
        once.
      * ``mesh``: run tensor-parallel on this device mesh.  The page
        pools are sharded over its 'model' axis (KV heads when the
        arch's GQA count divides it, physical pages otherwise — see
        ``partitioning.paged_pool_pspec``) and both serving phases
        attend through the shard_map dispatchers in
        ``kernels/lut_attention/sharded_paged.py``; page allocation
        interleaves across device slabs.  Output stays token-identical
        to the single-device engine.
      * ``shard_params``: with a mesh, place the weights TP-sharded
        (``partitioning.make_param_shardings(fsdp=False)``) instead of
        replicated.  Replicated (the default) keeps every computation
        outside the attention shard_maps bitwise the single-device
        program; sharded is the production memory/throughput layout and
        may reassociate matmul reductions at roundoff level.

    The pre-config keyword arguments (``n_slots=``, ``cache=``, ...)
    are still accepted for one release: they build the equivalent
    ``EngineConfig`` under a ``DeprecationWarning``.
    """

    def __init__(self, model: Model, params, run: RunConfig,
                 config: EngineConfig | None = None, **kwargs):
        if kwargs:
            if config is not None:
                raise TypeError(
                    "pass EngineConfig(...) or legacy kwargs, not both: "
                    f"{sorted(kwargs)}")
            unknown = set(kwargs) - _LEGACY_ENGINE_KWARGS
            if unknown:
                raise TypeError(
                    f"unknown ServingEngine arguments: {sorted(unknown)}")
            warnings.warn(
                "ServingEngine(n_slots=, cache=, ...) keyword arguments "
                "are deprecated; pass config=EngineConfig(...) instead",
                DeprecationWarning, stacklevel=2)
            config = EngineConfig(**kwargs)
        elif config is None:
            config = EngineConfig()
        if model.is_encdec:
            raise NotImplementedError("engine serves decoder-only LMs")
        TF.check_paged_supported(model.cfg)
        if config.prefill_chunk < 1:
            raise ValueError(f"prefill_chunk {config.prefill_chunk} < 1")
        if config.prefill_budget is not None and config.prefill_budget < 1:
            raise ValueError(f"prefill_budget {config.prefill_budget} < 1")
        if config.shard_params and config.mesh is None:
            raise ValueError("shard_params=True requires a mesh")
        from repro.runtime import partitioning as PT
        from repro.runtime.paged_cache import KV_DTYPES
        self.config = config
        mesh = config.mesh
        cache = config.cache
        # resolve the pool storage dtype: the engine knob (when set)
        # overrides the run's, and the resolved value flows everywhere
        # through ONE RunConfig — pools, scatter, attention dispatch
        if config.kv_dtype is not None and config.kv_dtype != run.kv_dtype:
            run = dataclasses.replace(run, kv_dtype=config.kv_dtype)
        if run.kv_dtype not in KV_DTYPES:
            raise ValueError(
                f"unknown kv_dtype {run.kv_dtype!r}: expected one of "
                f"{KV_DTYPES}")
        # Runtime mirror of the kernel guard's static overflow proof: the
        # integer Σ is accumulated in f32 (exact below 2^24), so rows may
        # carry at most max_lk = SIGMA_ACC_LIMIT // qmax keys.
        policy = run.softmax_policy
        if policy.impl != "exact":
            from repro.core.precision import get_precision
            bound = get_precision(policy.precision).max_lk
            if cache.max_context > bound:
                raise ValueError(
                    f"cache max_context {cache.max_context} exceeds the "
                    f"integer-Σ overflow bound max_lk={bound} for "
                    f"{policy.impl}/{policy.precision}: qmax·Lk must stay "
                    f"under the f32-exact Σ limit; shrink max_pages_per_seq"
                    f"·page_size or pick a narrower table precision")
        self.mesh = mesh
        self.tp = PT.mesh_model_tp(mesh)
        if mesh is not None:
            params = jax.tree_util.tree_map(
                jax.device_put, params,
                PT.serving_param_shardings(params, mesh,
                                           config.shard_params))
        self.model = model
        self.params = params
        self.run_cfg = run
        self.cache = cache
        self.n_slots = config.n_slots
        self.prefill_chunk = config.prefill_chunk
        self.prefill_budget = (config.prefill_budget
                               if config.prefill_budget is not None
                               else config.prefill_chunk)
        self.scheduler = Scheduler(cache, config.n_slots, tp=self.tp,
                                   prefix_cache=config.prefix_cache)
        self.pools = model.init_paged_pools(cache.n_pages, cache.page_size,
                                            run, mesh=mesh)
        self.stats = EngineStats()
        self._results: dict[int, GenerationResult] = {}
        self._seqs: dict[int, Sequence] = {}
        self._t_added: dict[int, float] = {}
        self._ttft: dict[int, float] = {}
        self._on_token: dict[int, Callable[[int], None]] = {}
        self._n_streamed: dict[int, int] = {}
        self._last_decode_end: float | None = None
        self._next_id = 0

        def chunk_fn(params, tokens, pools, block_tables, cache_lens,
                     chunk_lens):
            return model.prefill_chunk_paged(params, tokens, pools,
                                             block_tables, cache_lens,
                                             chunk_lens, run)

        def decode_fn(params, token, pools, block_tables, lengths):
            return model.decode_step_paged(params, token, pools,
                                           block_tables, lengths, run)

        from repro.kernels.lut_attention.ops import paged_mesh_regime
        page_sharded = (paged_mesh_regime(mesh, model.cfg.n_kv_heads)
                        == "pages")

        def copy_page_fn(pools, src, dst):
            # duplicate one physical page across every pool leaf (axis 0
            # is the period stack, axis 1 the page id) — the device half
            # of a copy-on-write: bitwise, so sharing stays invisible
            if not page_sharded:
                # page axis unsharded: a one-page in-place scatter
                return jax.tree_util.tree_map(
                    lambda v: v.at[:, dst].set(v[:, src]), pools)

            def dup(v):
                # page axis sharded: dynamic-slice with a traced page id
                # would make SPMD all-gather the whole pool (KV-sized —
                # caught by the tp-pages cow-copy contract).  A one-hot
                # select reduces over the sharded axis instead, so only
                # the one selected page is psum'd, then the write back
                # is element-wise and shard-local.
                pages = jnp.arange(v.shape[1])
                sel = pages.reshape((1, -1) + (1,) * (v.ndim - 2))
                page = jnp.sum(jnp.where(sel == src, v, 0), axis=1,
                               keepdims=True)
                return jnp.where(sel == dst, page, v)

            return jax.tree_util.tree_map(dup, pools)

        # donate the pools: the old buffers are dead the moment the step
        # returns, so XLA may scatter the new K/V in place (a no-op on
        # CPU, where donation is unimplemented, but the serving intent)
        jit = config.jit
        self._chunk_fn = (jax.jit(chunk_fn, donate_argnums=(2,))
                          if jit else chunk_fn)
        self._decode_fn = (jax.jit(decode_fn, donate_argnums=(2,))
                           if jit else decode_fn)
        if jit and mesh is not None:
            # pin the output placement: page ids are replicated scalars,
            # so without this the copy could silently re-layout the
            # sharded pool on its first trace
            pool_sh = jax.tree_util.tree_map(
                lambda v: PT.paged_pool_sharding(mesh, model.cfg.n_kv_heads,
                                                 stacked=True,
                                                 scales=(v.ndim == 4)),
                self.pools)
            self._copy_fn = jax.jit(copy_page_fn, donate_argnums=(0,),
                                    out_shardings=pool_sh)
        elif jit:
            self._copy_fn = jax.jit(copy_page_fn, donate_argnums=(0,))
        else:
            self._copy_fn = copy_page_fn

    # -- public API -------------------------------------------------------

    def add_request(self, prompt, max_new_tokens: int, *,
                    temperature: float = 0.0, seed: int = 0,
                    eos_id: int | None = None,
                    on_token: Callable[[int], None] | None = None,
                    ) -> RequestHandle:
        """Queue a request; returns its :class:`RequestHandle` (which
        hashes/compares as the bare integer id it used to return).

        ``on_token`` streams: it is called once per generated token, in
        order, from whichever thread drives :meth:`step`.  Tokens
        re-generated after an eviction are NOT re-emitted (replay is
        deterministic, so the stream just resumes where it left off).
        """
        rid = self._next_id
        self._next_id += 1
        self._seqs[rid] = self.scheduler.add(Request(
            # lint: allow-host-sync — caller-provided prompt, host data
            id=rid, prompt=tuple(int(t) for t in np.asarray(prompt)),
            max_new_tokens=max_new_tokens, temperature=temperature,
            seed=seed, eos_id=eos_id))
        self._t_added[rid] = time.perf_counter()
        if on_token is not None:
            self._on_token[rid] = on_token
            self._n_streamed[rid] = 0
        self.stats.queue_depth_peak = max(self.stats.queue_depth_peak,
                                          len(self.scheduler.waiting))
        return RequestHandle(rid, self)

    def has_work(self) -> bool:
        """Anything left to drive (queued, slotted, or — on the
        pipelined engine — dispatched and awaiting harvest)."""
        return self.scheduler.has_work()

    def step(self) -> list[GenerationResult]:
        """Admit + COW page copies + budgeted prefill chunks + one
        decode step.

        Returns requests finished this step.
        """
        finished: list[Sequence] = []
        for seq, n in self._plan_prefill():
            if self._prefill_chunk_step(seq, n):
                finished.append(seq)
        decode = self._plan_decode()
        if decode:
            finished.extend(self._decode_step(decode))
        return self._finish_step(finished)

    def run(self, requests: SeqOf[tuple] | None = None,
            ) -> dict[int, GenerationResult]:
        """Drive queued (plus optionally given) requests to completion.

        ``requests`` items are (prompt, max_new_tokens) pairs or dicts of
        :meth:`add_request` kwargs.
        """
        t0 = time.perf_counter()
        for r in requests or ():
            if isinstance(r, dict):
                self.add_request(**r)
            else:
                self.add_request(r[0], r[1])
        self._last_decode_end = None  # stall metric is per drive
        out: dict[int, GenerationResult] = {}
        while self.has_work():
            for res in self.step():
                out[res.request_id] = res
        self.stats.wall_s += time.perf_counter() - t0
        return out

    # -- internals --------------------------------------------------------
    #
    # The driver thread's host phases run under profiler spans
    # (``jax.profiler.TraceAnnotation``: recorded only while a trace is
    # active, on the clock of the device ops): ``engine.plan``,
    # ``engine.dispatch_chunk``, ``engine.dispatch_decode``,
    # ``engine.harvest_wait`` and ``engine.resolve``.  They never nest in
    # each other, so their summed durations are the host's self time.

    def _plan_prefill(self) -> list[tuple[Sequence, int]]:
        """Admission, queued copy-on-write page copies, and this step's
        budgeted prefill chunks."""
        with jax.profiler.TraceAnnotation("engine.plan"):
            while self.scheduler.try_admit() is not None:
                pass
            self._run_pending_copies()
            return self.scheduler.plan_prefill(self.prefill_chunk,
                                               self.prefill_budget)

    def _plan_decode(self) -> dict[int, Sequence]:
        """The slots that decode this step, their pages grown for the
        new token (eviction may shrink the set)."""
        with jax.profiler.TraceAnnotation("engine.plan"):
            if not self.scheduler.decode_slots():
                return {}
            self.scheduler.grow_for_decode()
            return self.scheduler.decode_slots()

    def _finish_step(self, finished: list[Sequence]
                     ) -> list[GenerationResult]:
        """Sync the scheduler's counters and record finished requests."""
        with jax.profiler.TraceAnnotation("engine.resolve"):
            self._sync_sched_stats()
            return [self._record(seq) for seq in finished]

    @contextlib.contextmanager
    def _mesh_ctx(self):
        """Activate the engine's mesh around a device step.

        The paged attention paths in ``models/layers.py`` read the
        active-mesh context (the established idiom for the lockstep
        sharded decode), so it must be set when the jitted step
        *traces*; restoring the previous value keeps a single-device
        engine in the same process unaffected.
        """
        if self.mesh is None:
            yield
            return
        from repro.runtime import partitioning as PT
        prev = PT.active_mesh()
        PT.set_active_mesh(self.mesh)
        try:
            yield
        finally:
            PT.set_active_mesh(prev)

    def _run_pending_copies(self) -> None:
        """Execute the scheduler's queued copy-on-write page copies.

        Runs *before* any prefill chunk of this step: admission queued
        the copy exactly so that the step's scatter targets a privately
        owned duplicate.  Page ids ship as traced int32 scalars — one
        compile serves every (src, dst) pair — and copies are rare (one
        per fully-resident prompt), so a host-side loop over pairs beats
        a shape-polymorphic batched variant.
        """
        if not self.scheduler.pending_copies:
            return
        copies, self.scheduler.pending_copies = \
            self.scheduler.pending_copies, []
        if self.mesh is None:
            put = jnp.int32
        else:
            from repro.runtime import partitioning as PT
            rep = PT.replicated_sharding(self.mesh)
            put = lambda i: jax.device_put(np.int32(i), rep)  # noqa: E731
        with self._mesh_ctx():
            for src, dst in copies:
                self.pools = self._copy_fn(self.pools, put(src), put(dst))
        self.scheduler.confirm_copies(copies)

    def _prefill_chunk_step(self, seq: Sequence, n: int) -> bool:
        """Push one prompt chunk into the pool; True if the request
        finished outright (single-token budgets / instant EOS)."""
        view = view_arrays(
            prefill_chunk_view(seq, n, self.prefill_chunk, self.cache),
            self.mesh)
        with self._mesh_ctx():
            logits, self.pools = self._chunk_fn(
                self.params, view.tokens, self.pools, view.block_tables,
                view.cache_lens, view.chunk_lens)
        self.stats.prefill_steps += 1
        self.stats.prompt_tokens += n
        if not self.scheduler.on_prefill_chunk(seq, n):
            return False
        # prompt complete: the chunk's last-valid-position logits are the
        # whole-prompt logits — sample the first token right here
        self.stats.prefills += 1
        self.stats.first_tokens += 1
        # lint: allow-host-sync — sync engine only: the prompt's first
        # token is host-sampled from the final chunk's logits; the
        # pipelined engine replaces this path with on-device sampling
        tok = self._sample(seq, np.asarray(logits[0, 0]))
        # stamp TTFT only now: np.asarray above blocked on the device, so
        # the first token actually exists (async dispatch would otherwise
        # exclude the final chunk's compute from the metric)
        self._stamp_ttft(seq.request.id)
        done = self.scheduler.on_token(seq, tok)
        self._emit_new_tokens(seq)
        return done

    def _decode_view(self, running: dict[int, Sequence]):
        """The decode step's device view, its kernel blocks counted."""
        host = decode_view(running, self.n_slots, self.cache)
        mp = self.cache.max_pages_per_seq
        pages = decode_block_pages(self.cache.page_size, mp)
        kv_lens = host.lengths.astype(np.int64) + 1  # incl. the new token
        self.stats.decode_kv_blocks += len(kv_lens) * (mp // pages)
        self.stats.decode_kv_blocks_live += int(np.sum(
            -(-kv_lens // (pages * self.cache.page_size))))
        return view_arrays(host, self.mesh)

    def _decode_step(self, running: dict[int, Sequence]) -> list[Sequence]:
        """One batched decode step over the running slots."""
        view = self._decode_view(running)
        with self._mesh_ctx():
            logits, self.pools = self._decode_fn(
                self.params, view.tokens, self.pools, view.block_tables,
                view.lengths)
        # lint: allow-host-sync — sync engine only: ServingEngine samples
        # on the host each step by design; PipelinedEngine overrides the
        # whole step loop and never fetches logits (contract-checked)
        logits = np.asarray(logits)  # (n_slots, 1, V)
        # stall metric: completion-to-completion, measured AFTER the sync
        # above — un-synced prefill chunks queue device work that
        # surfaces in the next decode completion, so chunked and
        # monolithic prefill are charged identically (dispatch-time gaps
        # would under-count the chunked mode's stall on async backends)
        now = time.perf_counter()
        if self._last_decode_end is not None:
            self.stats.max_decode_gap_s = max(
                self.stats.max_decode_gap_s, now - self._last_decode_end)
        self._last_decode_end = now
        self.stats.steps += 1
        finished = []
        for slot, seq in running.items():
            tok = self._sample(seq, logits[slot, 0])
            self.stats.decode_tokens += 1
            if self.scheduler.on_token(seq, tok):
                finished.append(seq)
            self._emit_new_tokens(seq)
        return finished

    def _sync_sched_stats(self) -> None:
        # sync unconditionally: eviction counts must be visible even on
        # steps where every slot drained (used to lag behind one step)
        self.stats.preemptions = self.scheduler.n_preemptions
        self.stats.prefix_hit_tokens = self.scheduler.prefix_hit_tokens
        self.stats.pages_shared = self.scheduler.pages_shared
        self.stats.cow_copies = self.scheduler.cow_copies
        self.stats.queue_depth_peak = max(self.stats.queue_depth_peak,
                                          len(self.scheduler.waiting))

    def _stamp_ttft(self, rid: int) -> None:
        if rid in self._ttft:
            return
        # a missing admission timestamp would silently report ~0 TTFT
        # through a `.get(rid, now)` fallback — every path into the
        # scheduler goes through add_request, so it's a bug
        assert rid in self._t_added, \
            f"request {rid} has no admission timestamp"
        self._ttft[rid] = time.perf_counter() - self._t_added[rid]

    def _emit_new_tokens(self, seq: Sequence) -> None:
        """Stream resolved tokens past the per-request watermark."""
        rid = seq.request.id
        cb = self._on_token.get(rid)
        if cb is None:
            return
        start = self._n_streamed.get(rid, 0)
        emit = []
        for tok in seq.generated[start:]:
            if tok == PENDING_TOKEN:
                break  # dispatched but not yet harvested
            emit.append(tok)
        # watermark BEFORE the callbacks: eviction replay regenerates
        # tokens [0, start) bit-identically, so they must not re-emit
        self._n_streamed[rid] = start + len(emit)
        for tok in emit:
            cb(tok)

    def _sample(self, seq: Sequence, logits_row: np.ndarray) -> int:
        req = seq.request
        if req.temperature <= 0.0:
            return int(np.argmax(logits_row))
        key = jax.random.fold_in(jax.random.PRNGKey(req.seed),
                                 len(seq.generated))
        return int(jax.random.categorical(
            key, jnp.asarray(logits_row) / req.temperature))

    def _record(self, seq: Sequence) -> GenerationResult:
        rid = seq.request.id
        res = GenerationResult(
            request_id=rid,
            # lint: allow-host-sync — host-side token list, no device wait
            tokens=np.asarray(seq.generated, np.int32),
            finish_reason=seq.finish_reason or "length",
            n_evictions=seq.n_evictions,
            ttft_s=self._ttft.pop(rid, None),  # drop per-request timing
            prefix_hit_tokens=seq.prefix_hit_tokens)
        self._t_added.pop(rid, None)           # state with the result
        self._seqs.pop(rid, None)
        self._on_token.pop(rid, None)
        self._n_streamed.pop(rid, None)
        self._results[rid] = res
        return res


@dataclasses.dataclass
class _InflightStep:
    """One dispatched-but-unharvested device step."""

    tokens: jax.Array    # (n_slots,) decode / (1,) final chunk, int32
    #: (seq, index into ``tokens``, position in ``seq.generated`` this
    #: token resolves, ``seq.n_evictions`` at dispatch) — the epoch lets
    #: harvest drop tokens whose sequence was evicted after dispatch
    #: (replay regenerates them bit-identically).
    entries: list[tuple[Sequence, int, int, int]]
    kind: str            # 'decode' | 'chunk'
    step: int            # the ``step`` argument of its host spans


class PipelinedEngine(ServingEngine):
    """:class:`ServingEngine` with host scheduling overlapped onto
    device compute.

    Two changes, same token streams:

    * **On-device sampling.**  The decode / final-prefill-chunk programs
      end in ``serve_loop.sample_tokens`` — greedy argmax or
      ``categorical(fold_in(PRNGKey(seed), position))``, bitwise the
      host path — so a step returns an ``(n_slots,)`` int32 token array
      instead of shipping ``(n_slots, 1, V)`` logits across the host
      boundary every token.
    * **One-step-ahead dispatch.**  The step loop keeps up to
      ``config.pipeline_depth`` device steps in flight: step N+1 is
      planned and dispatched from step N's *dispatched-but-unfetched*
      tokens, which live in a device-resident ``(n_slots,)`` last-token
      buffer (each decode's sampled output IS the next decode's input —
      the host never needs the values to plan).  Host-side bookkeeping
      marks the speculated positions :data:`PENDING_TOKEN` and resolves
      them when the step is harvested.

    The speculation rule: **length**-finishes are known at dispatch
    (token count, not token value) and retire the slot immediately;
    **EOS** is only visible one harvest later, so a sequence that hits
    EOS has dispatched at most ONE extra slot-step, which harvest rolls
    back (truncating the speculated tail — ``stats.speculative_wasted``
    counts the waste).  Eviction during speculation is handled by
    epoch-tagging in-flight tokens: stale ones are dropped and replay
    regenerates them identically.  Page reuse across in-flight steps is
    safe because the pool arrays thread functionally through the jitted
    steps — step N+1's writes cannot be reordered before step N's reads.

    Token streams are identical to :class:`ServingEngine` (and lockstep
    ``generate``) by the same invariances the test suite pins for the
    sync engine: sampling keys off ``(seed, position)`` only, and
    batch-composition / eviction-replay / page-placement invariance make
    the altered *scheduling* unobservable in the output.
    """

    def __init__(self, model: Model, params, run: RunConfig,
                 config: EngineConfig | None = None, **kwargs):
        super().__init__(model, params, run, config, **kwargs)
        config = self.config
        if config.pipeline_depth < 1:
            raise ValueError(f"pipeline_depth {config.pipeline_depth} < 1")
        self.depth = config.pipeline_depth
        self._inflight: deque[_InflightStep] = deque()
        self._n_dispatched = 0   # device programs launched (span ``step``)
        run = self.run_cfg  # parent resolved the kv_dtype override

        # `greedy` is static under jit: an all-greedy batch compiles a
        # variant with no threefry/gumbel work at all (two traces max)
        def decode_sampled_fn(params, tokens, pools, block_tables, lengths,
                              seeds, positions, temps, greedy):
            logits, new_pools = model.decode_step_paged(
                params, tokens[:, None], pools, block_tables, lengths, run)
            return (sample_tokens(logits, seeds, positions, temps,
                                  greedy=greedy), new_pools)

        def chunk_sampled_fn(params, tokens, pools, block_tables,
                             cache_lens, chunk_lens, seeds, positions,
                             temps, greedy):
            logits, new_pools = model.prefill_chunk_paged(
                params, tokens, pools, block_tables, cache_lens, chunk_lens,
                run)
            return (sample_tokens(logits, seeds, positions, temps,
                                  greedy=greedy), new_pools)

        def set_tok_fn(buf, slot, tok):
            # write a final-chunk first token into the last-token buffer
            # (slot is a traced scalar: one compile serves every slot)
            return buf.at[slot].set(tok[0])

        jit = config.jit
        if jit and self.mesh is not None:
            from repro.runtime import partitioning as PT
            rep = PT.replicated_sharding(self.mesh)
            pool_sh = jax.tree_util.tree_map(
                lambda v: PT.paged_pool_sharding(self.mesh,
                                                 model.cfg.n_kv_heads,
                                                 stacked=True,
                                                 scales=(v.ndim == 4)),
                self.pools)
            self._decode_sampled_fn = jax.jit(
                decode_sampled_fn, donate_argnums=(2,), static_argnums=(8,),
                out_shardings=(rep, pool_sh))
            self._chunk_sampled_fn = jax.jit(
                chunk_sampled_fn, donate_argnums=(2,), static_argnums=(9,),
                out_shardings=(rep, pool_sh))
            self._set_tok_fn = jax.jit(set_tok_fn, out_shardings=rep)
            self._token_buf = jax.device_put(
                np.zeros((self.n_slots,), np.int32), rep)
        else:
            if jit:
                self._decode_sampled_fn = jax.jit(decode_sampled_fn,
                                                  donate_argnums=(2,),
                                                  static_argnums=(8,))
                self._chunk_sampled_fn = jax.jit(chunk_sampled_fn,
                                                 donate_argnums=(2,),
                                                 static_argnums=(9,))
                self._set_tok_fn = jax.jit(set_tok_fn)
            else:
                self._decode_sampled_fn = decode_sampled_fn
                self._chunk_sampled_fn = chunk_sampled_fn
                self._set_tok_fn = set_tok_fn
            self._token_buf = jnp.zeros((self.n_slots,), jnp.int32)
        # an all-greedy step never reads the sampling metadata: reuse
        # cached zero arrays instead of three device_puts per dispatch
        self._zero_meta_decode = self._put_sample_meta(
            [0] * self.n_slots, [0] * self.n_slots, [0.0] * self.n_slots)
        self._zero_meta_chunk = self._put_sample_meta([0], [0], [0.0])

    # -- small host→device helpers ----------------------------------------

    def _put(self, a: np.ndarray):
        if self.mesh is None:
            return jnp.asarray(a)
        from repro.runtime import partitioning as PT
        return jax.device_put(a, PT.replicated_sharding(self.mesh))

    def _put_sample_meta(self, seeds, positions, temps):
        # lint: allow-host-sync — host lists H2D, no device wait
        seeds = np.asarray(seeds, np.int32)
        # lint: allow-host-sync
        positions = np.asarray(positions, np.int32)
        # lint: allow-host-sync
        temps = np.asarray(temps, np.float32)
        return self._put(seeds), self._put(positions), self._put(temps)

    # -- step loop ---------------------------------------------------------

    def step(self) -> list[GenerationResult]:
        """Harvest until under the in-flight cap, dispatch one step's
        plan, and — when there was nothing to dispatch — drain one
        in-flight step so the loop always makes progress.
        """
        finished: list[Sequence] = []
        while len(self._inflight) >= self.depth:
            finished.extend(self._harvest())
        if not self._dispatch() and self._inflight:
            finished.extend(self._harvest())
        return self._finish_step(finished)

    def has_work(self) -> bool:
        return self.scheduler.has_work() or bool(self._inflight)

    # -- dispatch (plan one step ahead) ------------------------------------

    def _dispatch(self) -> bool:
        """Plan and launch one engine step's device work (admission,
        COW copies, budgeted prefill chunks, one decode batch) without
        waiting for any of it.  False when there was nothing to do."""
        dispatched = False
        for seq, n in self._plan_prefill():
            with self._dispatch_span("engine.dispatch_chunk"):
                self._dispatch_chunk(seq, n)
            dispatched = True
        decode = self._plan_decode()
        if decode:
            with self._dispatch_span("engine.dispatch_decode"):
                self._dispatch_decode(decode)
            dispatched = True
        return dispatched

    def _dispatch_span(self, name: str):
        """Host span of one program's launch; the harvest of the same
        program carries the same ``step``."""
        self._n_dispatched += 1
        return jax.profiler.TraceAnnotation(name, step=self._n_dispatched)

    def _dispatch_chunk(self, seq: Sequence, n: int) -> None:
        """Launch one prompt chunk; the final chunk fuses first-token
        sampling and joins the in-flight queue."""
        final = seq.prefilled + n == seq.prompt_len
        view = view_arrays(
            prefill_chunk_view(seq, n, self.prefill_chunk, self.cache),
            self.mesh)
        if not final:
            with self._mesh_ctx():
                _, self.pools = self._chunk_fn(
                    self.params, view.tokens, self.pools, view.block_tables,
                    view.cache_lens, view.chunk_lens)
            self.stats.prefill_steps += 1
            self.stats.prompt_tokens += n
            self.scheduler.on_prefill_chunk(seq, n)
            return
        req = seq.request
        greedy = req.temperature <= 0.0
        if greedy:
            seeds, positions, temps = self._zero_meta_chunk
        else:
            # the first token samples at position 0 — len(seq.generated)
            # is 0 here even on re-admission (eviction cleared it)
            seeds, positions, temps = self._put_sample_meta(
                [req.seed], [0], [req.temperature])
        with self._mesh_ctx():
            toks, self.pools = self._chunk_sampled_fn(
                self.params, view.tokens, self.pools, view.block_tables,
                view.cache_lens, view.chunk_lens, seeds, positions, temps,
                greedy)
        self.stats.prefill_steps += 1
        self.stats.prompt_tokens += n
        self.scheduler.on_prefill_chunk(seq, n)   # → RUNNING, owns a slot
        self.stats.prefills += 1
        self.stats.first_tokens += 1
        self._token_buf = self._set_tok_fn(self._token_buf,
                                           self._put(np.int32(seq.slot)),
                                           toks)
        self._push_inflight(toks, [(seq, 0, 0, seq.n_evictions)], "chunk")
        self.scheduler.on_token_speculative(seq)

    def _dispatch_decode(self, running: dict[int, Sequence]) -> None:
        """Launch one batched decode step from the device-resident
        last-token buffer."""
        # the view is built BEFORE the speculative append: lengths must
        # count only tokens whose K/V the pool already holds (plus the
        # input token, written by this step) — exactly the sync math
        view = self._decode_view(running)
        seeds = [0] * self.n_slots
        positions = [0] * self.n_slots
        temps = [0.0] * self.n_slots
        entries = []
        for slot, seq in running.items():
            req = seq.request
            seeds[slot] = req.seed
            positions[slot] = len(seq.generated)
            temps[slot] = req.temperature
            entries.append((seq, slot, len(seq.generated), seq.n_evictions))
        greedy = all(t <= 0.0 for t in temps)
        if greedy:
            s, p, t = self._zero_meta_decode
        else:
            s, p, t = self._put_sample_meta(seeds, positions, temps)
        with self._mesh_ctx():
            toks, self.pools = self._decode_sampled_fn(
                self.params, self._token_buf, self.pools, view.block_tables,
                view.lengths, s, p, t, greedy)
        # the sampled batch IS the next step's input buffer: empty slots
        # get garbage tokens, but their rows are dead (null block table,
        # zero length) and a slot re-admission overwrites via the final
        # chunk's _set_tok_fn before the slot decodes again
        self._token_buf = toks
        self.stats.steps += 1
        self.stats.decode_tokens += len(running)
        self._push_inflight(toks, entries, "decode")
        for seq in running.values():
            self.scheduler.on_token_speculative(seq)

    def _push_inflight(self, toks, entries, kind: str) -> None:
        if hasattr(toks, "copy_to_host_async"):
            toks.copy_to_host_async()  # overlap D2H with the next dispatch
        self._inflight.append(_InflightStep(toks, entries, kind,
                                            self._n_dispatched))
        self.stats.inflight_peak = max(self.stats.inflight_peak,
                                       len(self._inflight))

    # -- harvest (resolve one step late) -----------------------------------

    def _harvest(self) -> list[Sequence]:
        """Fetch the oldest in-flight step's tokens and resolve them.

        Returns sequences that finished AND have no pending positions
        left (i.e. are ready to record).
        """
        rec = self._inflight.popleft()
        with jax.profiler.TraceAnnotation("engine.harvest_wait",
                                          step=rec.step):
            # lint: allow-host-sync — the pipelined engine's ONE intended
            # device wait: harvesting a step dispatched `depth` steps
            # ago, and only the (n,) int32 sampled tokens — never full
            # logits (the decode-sampled contract pins the shape); D2H
            # was started early by copy_to_host_async at dispatch.  The
            # wait is a bare block_until_ready, so that the device's
            # gaps fall under this span and not under np.asarray's own
            rec.tokens.block_until_ready()
            host = np.asarray(rec.tokens)  # lint: allow-host-sync
        with jax.profiler.TraceAnnotation("engine.resolve", step=rec.step):
            return self._resolve(rec, host)

    def _resolve(self, rec: _InflightStep, host: np.ndarray
                 ) -> list[Sequence]:
        """Write a harvested step's tokens into its sequences: EOS
        rollback of the speculated tail, streaming, finish checks."""
        now = time.perf_counter()
        if rec.kind == "decode":
            # completion-to-completion stall metric, as in the sync path
            if self._last_decode_end is not None:
                self.stats.max_decode_gap_s = max(
                    self.stats.max_decode_gap_s,
                    now - self._last_decode_end)
            self._last_decode_end = now
        done: list[Sequence] = []
        for seq, bidx, idx, epoch in rec.entries:
            if seq.n_evictions != epoch:
                continue  # evicted after dispatch; replay regenerates it
            gen = seq.generated
            if idx >= len(gen) or gen[idx] != PENDING_TOKEN:
                continue  # rolled back by an earlier EOS resolution
            tok = int(host[bidx])
            gen[idx] = tok
            req = seq.request
            if idx == 0:
                self._stamp_ttft(req.id)
            if req.eos_id is not None and tok == req.eos_id:
                # EOS surfaced one step late: drop the speculated tail
                # (at most one slot-step per the dispatch rule)
                wasted = len(gen) - (idx + 1)
                del gen[idx + 1:]
                self.stats.speculative_wasted += wasted
                if seq.state is not SeqState.FINISHED:
                    self.scheduler.finish(seq, "eos")
                else:
                    seq.finish_reason = "eos"  # length-cut was also EOS
            self._emit_new_tokens(seq)
            if (seq.state is SeqState.FINISHED
                    and PENDING_TOKEN not in gen):
                done.append(seq)
        return done
