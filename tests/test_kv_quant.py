"""Accuracy + parity harness for the int8 quantized KV page pool.

Three layers of evidence that storing K/V pages as int8 with per-token ×
KV-head f32 scales costs less than the paper's accuracy budget:

* **Rounding-convention pins** — ``core.quantization``'s
  ``quantize_rows`` / ``dequantize_rows`` pair (the ONE convention the
  lockstep fake-quant branch and the engine's real int8 pool share):
  round-trip error ≤ scale/2, zero rows round-trip to exact zeros, the
  grid is a fixed point, and ``fake_quant_affine``'s zero-point stays on
  the integer grid at the one-sided-range boundaries.

* **Kernel parity** — the int8 Pallas kernels (interpret mode) against
  the dense dequantize-then-reference path on the SAME quantized pool:
  roundoff-equal across every policy, GQA ratio, ragged ``kv_lens``, and
  bitwise invariant under block-table permutation and junk-page
  poisoning (the indirection plumbs scales exactly like pages).

* **End-to-end degradation budget** — exact-vs-int8 over seeded
  workloads, teacher-forced so one hairline argmax flip cannot cascade
  into a divergent suffix (free-running greedy streams of a random toy
  model amplify a single coin-flip step into ~50% raw stream mismatch —
  that measures chaos, not quantization).  Per policy the harness pins
  per-step logit max-abs / relative deltas and asserts the *net*
  greedy-decision degradation (gold-accuracy drop vs the exact-f32
  stream, on steps whose decision margin exceeds the int8 resolution
  floor) stays under the 1 % budget.  The engine side rides the fuzz
  suite's bitwise engine≡lockstep pins (``test_engine_fuzz.py``), so
  lockstep deltas ARE engine deltas; a confident-prompt first-token
  engine run closes the loop without the cascade artifact.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import strategies
from repro.configs import ARCHS, RunConfig
from repro.core.policies import SoftmaxPolicy
from repro.core.quantization import (INT8_QMAX, dequantize_rows,
                                     fake_quant_affine, fake_quant_rows,
                                     quantize_rows)
from repro.kernels.lut_attention import ops
from repro.models import build_model
from repro.runtime import EngineConfig, PagedCacheConfig, ServingEngine
from repro.runtime.paged_cache import KV_DTYPES, pool_leaf_specs

POLICIES = strategies.make_policies()

TOL = dict(rtol=2e-6, atol=2e-6)

#: per-policy deltas of the seeded harness below (max-abs / relative,
#: worst of seeds 0–2 with JAX 0.9's weight draws: exact 0.055 / 0.007,
#: rexp 2.29 / 0.302, lut2d 0.67 / 0.087).  The LUT policies amplify the
#: int8 noise through their bucket edges — a K perturbation that crosses
#: a bucket moves that weight by a full quantum — so their absolute
#: deltas are policy noise, not broken scales; broken scales land at the
#: logit range (~9) and trip every pin at once.  REXP's relative delta
#: depends on the draw of the random weights: JAX 0.5 changed the
#: default PRNG (``jax_threefry_partitionable``), and the same
#: ``PRNGKey(0)`` that gave 0.156 under the old draws gives 0.302 now,
#: while init keys 1 and 2 give 0.397 and 0.354 — at an unchanged
#: degradation ≤ 0.64 %.  Its pin sits above that spread.
LOGIT_BUDGETS = {
    "exact": dict(max_abs=0.2, rel=0.01),
    "rexp": dict(max_abs=2.6, rel=0.45),
    "lut2d": dict(max_abs=1.8, rel=0.20),
}
#: the paper-facing accuracy budget: net greedy-decision degradation
DEGRADATION_BUDGET = 0.01


# ---------------------------------------------------------------------------
# Rounding-convention pins (core/quantization.py)
# ---------------------------------------------------------------------------


def test_quantize_rows_round_trip_bound(rng):
    x = jnp.asarray(rng.normal(size=(5, 7, 16)).astype(np.float32) * 3.0)
    q, scale = quantize_rows(x)
    assert q.dtype == jnp.int8
    assert scale.dtype == jnp.float32
    assert scale.shape == x.shape[:-1]
    assert np.all(np.asarray(scale) > 0)
    err = np.abs(np.asarray(dequantize_rows(q, scale)) - np.asarray(x))
    # symmetric rounding: per element at most half a quantization step
    assert np.all(err <= np.asarray(scale)[..., None] * 0.5 + 1e-7)


def test_quantize_rows_zero_rows_round_trip_to_exact_zero():
    x = jnp.zeros((3, 4, 8), jnp.float32)
    q, scale = quantize_rows(x)
    assert np.all(np.asarray(q) == 0)
    assert np.all(np.isfinite(np.asarray(scale)))  # tiny floor, not NaN/0
    np.testing.assert_array_equal(np.asarray(dequantize_rows(q, scale)),
                                  np.zeros_like(np.asarray(x)))


def test_fake_quant_rows_is_grid_fixed_point(rng):
    """Values already on the int8 grid must survive unchanged — the
    property that makes lockstep fake-quant ≡ engine quantize∘dequantize
    (both are one projection onto the same grid, never two)."""
    x = jnp.asarray(rng.normal(size=(4, 32)).astype(np.float32))
    once = fake_quant_rows(x)
    np.testing.assert_array_equal(np.asarray(fake_quant_rows(once)),
                                  np.asarray(once))
    # the max-|x| element is exactly representable (it defines the scale)
    amax_idx = np.abs(np.asarray(x)).argmax(axis=-1)
    rows = np.arange(x.shape[0])
    np.testing.assert_allclose(np.asarray(once)[rows, amax_idx],
                               np.asarray(x)[rows, amax_idx], rtol=1e-6)


def test_quantize_rows_extreme_magnitudes_stay_finite():
    x = jnp.asarray(np.array([[1e-30] * 4, [1e30] * 4, [0.0] * 4],
                             np.float32))
    out = np.asarray(fake_quant_rows(x))
    assert np.all(np.isfinite(out))
    np.testing.assert_array_equal(out[2], 0.0)


@pytest.mark.parametrize("sign", [1.0, -1.0])
def test_fake_quant_affine_one_sided_boundary(rng, sign):
    """The zero-point clamp at the one-sided-range boundary: an
    all-positive (all-negative) tensor clamps lo (hi) to 0, the
    zero-point lands on an integer grid point, and zero plus the range
    extremes stay exactly representable — the bug the shared helper
    fixed was a fractional zero-point drifting every round trip."""
    qmax = 255.0
    x = jnp.asarray(sign * (0.5 + rng.random((64,)).astype(np.float32)))
    out = np.asarray(fake_quant_affine(x, qmax))
    lo = min(float(jnp.min(x)), 0.0)
    hi = max(float(jnp.max(x)), 0.0)
    scale = (hi - lo) / qmax
    # every output sits on the affine grid (q - zp)·scale with integer q,
    # zp — i.e. outputs/scale are integers up to roundoff
    steps = out / scale
    np.testing.assert_allclose(steps, np.round(steps), atol=1e-3)
    # round trip within half a step, extreme exactly representable
    assert np.all(np.abs(out - np.asarray(x)) <= scale * 0.5 + 1e-6)
    ext = float(jnp.max(jnp.abs(x))) * sign
    assert abs(out[np.abs(np.asarray(x) - ext).argmin()] - ext) \
        <= scale * 0.5 + 1e-6
    # zero is exactly representable: quantizing a tensor containing 0
    x0 = jnp.concatenate([x, jnp.zeros((1,), jnp.float32)])
    assert np.asarray(fake_quant_affine(x0, qmax))[-1] == 0.0


def test_pool_leaf_specs_int8_contract():
    """The pool contract: int8 mode adds f32 scale leaves shaped
    (n_pages, 1, kvh·page_size) and cuts pool bytes to (dh + 4)/(4·dh)
    of the f32 layout — the VMEM/HBM headline the guard re-proves."""
    args = dict(n_pages=16, page_size=8, n_kv_heads=4, head_dim=32)
    f32 = pool_leaf_specs(**args)
    q = pool_leaf_specs(**args, kv_dtype="int8")
    assert set(f32) == {"k_pages", "v_pages"}
    assert set(q) == {"k_pages", "v_pages", "k_scales", "v_scales"}
    assert q["k_pages"][1] == "int8"
    assert q["k_scales"] == ((16, 1, 32), "float32")

    def nbytes(specs):
        return sum(int(np.prod(s)) * np.dtype(d).itemsize
                   for s, d in specs.values())

    ratio = nbytes(q) / nbytes(f32)
    assert ratio == pytest.approx((32 + 4) / (4 * 32))
    assert ratio < 0.55
    with pytest.raises(ValueError, match="kv_dtype"):
        pool_leaf_specs(**args, kv_dtype="int4")
    assert KV_DTYPES == ("f32", "int8")


# ---------------------------------------------------------------------------
# int8 kernel ≡ dense dequantized reference (interpret mode)
# ---------------------------------------------------------------------------


def _quantized_problem(rng, *, b=3, kvh=2, g=2, dh=16, ps=4, mp=5,
                       kv_lens=(20, 17, 9), lq=None):
    """Random paged problem with an int8 pool: quantize a dense f32 pool
    with the shared convention; slot i owns ceil(kv_lens[i]/ps) pages."""
    h = kvh * g
    n_pages = 1 + b * mp
    lq = 1 if lq is None else lq
    q = jnp.asarray(rng.normal(size=(b, h, lq, dh)).astype(np.float32))
    kf = jnp.asarray(rng.normal(size=(n_pages, kvh, ps, dh))
                     .astype(np.float32))
    vf = jnp.asarray(rng.normal(size=(n_pages, kvh, ps, dh))
                     .astype(np.float32))
    kq, ks = quantize_rows(kf)
    vq, vs = quantize_rows(vf)
    # (n_pages, KVH, ps) scales → the pool's head-major scale rows
    ks = ks.reshape(n_pages, 1, kvh * ps)
    vs = vs.reshape(n_pages, 1, kvh * ps)
    phys = rng.permutation(np.arange(1, n_pages))
    bt = np.zeros((b, mp), np.int32)
    for i, kl in enumerate(kv_lens):
        n_owned = -(-int(kl) // ps)
        bt[i, :n_owned] = phys[i * mp:i * mp + n_owned]
    return (q, kq, vq, ks, vs, jnp.asarray(bt),
            jnp.asarray(np.asarray(kv_lens, np.int32)))


@pytest.mark.parametrize("impl", sorted(POLICIES))
@pytest.mark.parametrize("g", [1, 4])
def test_int8_decode_kernel_matches_dense(rng, impl, g):
    pol = POLICIES[impl]
    q, kq, vq, ks, vs, bt, kls = _quantized_problem(rng, g=g,
                                                    kv_lens=(20, 17, 2))
    pal = ops.lut_attention_paged_decode(q, kq, vq, bt, kls, pol,
                                         backend="pallas",
                                         k_scales=ks, v_scales=vs)
    den = ops.lut_attention_paged_decode(q, kq, vq, bt, kls, pol,
                                         backend="dense",
                                         k_scales=ks, v_scales=vs)
    assert pal.shape == den.shape == q.shape
    np.testing.assert_allclose(np.asarray(pal), np.asarray(den), **TOL)


@pytest.mark.parametrize("impl", sorted(POLICIES))
@pytest.mark.parametrize("kv_lens", [(16, 16, 16), (1, 1, 1), (19, 3, 7)])
def test_int8_prefill_kernel_matches_dense(rng, impl, kv_lens):
    pol = POLICIES[impl]
    c = 4
    q, kq, vq, ks, vs, bt, kls = _quantized_problem(rng, kv_lens=kv_lens,
                                                    lq=c)
    q_start = jnp.maximum(kls - c, 0)
    pal = ops.lut_attention_paged_prefill(q, kq, vq, bt, q_start, kls, pol,
                                          backend="pallas",
                                          k_scales=ks, v_scales=vs)
    den = ops.lut_attention_paged_prefill(q, kq, vq, bt, q_start, kls, pol,
                                          backend="naive",
                                          k_scales=ks, v_scales=vs)
    assert pal.shape == den.shape == q.shape
    np.testing.assert_allclose(np.asarray(pal), np.asarray(den), **TOL)


@strategies.permutation_property()
def test_int8_block_table_permutation_invariance(seed, impl, kv_lens):
    """Relabelling physical pages — scales moving WITH their pages —
    changes nothing, bitwise: the scale indirection is the page
    indirection."""
    rng = np.random.default_rng(seed)
    pol = POLICIES[impl]
    q, kq, vq, ks, vs, bt, kls = _quantized_problem(
        rng, b=len(kv_lens), kv_lens=tuple(kv_lens))
    base = ops.lut_attention_paged_decode(q, kq, vq, bt, kls, pol,
                                          backend="pallas",
                                          k_scales=ks, v_scales=vs)
    perm, inv = strategies.pool_permutation(rng, kq.shape[0])
    inv = jnp.asarray(inv)
    out = ops.lut_attention_paged_decode(
        q, kq[inv], vq[inv], jnp.asarray(perm, jnp.int32)[bt], kls, pol,
        backend="pallas", k_scales=ks[inv], v_scales=vs[inv])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(base))


def test_int8_kernel_ignores_junk_pages_and_scales(rng):
    """Poisoning pages outside every block table — and their scales —
    must not change a single bit: unwritten scales can be anything."""
    pol = POLICIES["lut2d"]
    q, kq, vq, ks, vs, bt, kls = _quantized_problem(rng,
                                                    kv_lens=(9, 13, 5))
    ref = ops.lut_attention_paged_decode(q, kq, vq, bt, kls, pol,
                                         backend="pallas",
                                         k_scales=ks, v_scales=vs)
    owned = set()
    bt_np, ps = np.asarray(bt), kq.shape[2]
    for i, kl in enumerate(np.asarray(kls)):
        owned.update(bt_np[i, :-(-int(kl) // ps)])
    junk = jnp.asarray([p for p in range(kq.shape[0]) if p not in owned])
    out = ops.lut_attention_paged_decode(
        q, kq.at[junk].set(127), vq.at[junk].set(-127), bt, kls, pol,
        backend="pallas", k_scales=ks.at[junk].set(1e9),
        v_scales=vs.at[junk].set(1e9))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_scales_are_required_together():
    rng = np.random.default_rng(0)
    q, kq, vq, ks, _, bt, kls = _quantized_problem(rng)
    with pytest.raises(Exception):
        ops.lut_attention_paged_decode(q, kq, vq, bt, kls,
                                       POLICIES["exact"],
                                       backend="pallas", k_scales=ks)


# ---------------------------------------------------------------------------
# End-to-end accuracy: exact-vs-int8 under the 1 % degradation budget
# ---------------------------------------------------------------------------

VOCAB = 128
_CACHE = PagedCacheConfig(n_pages=40, page_size=4, max_pages_per_seq=8)


@pytest.fixture(scope="module")
def acc_lm():
    arch = ARCHS["qwen3-32b"].scaled_down(d_model=64, n_heads=4,
                                          vocab=VOCAB, n_periods=1)
    model = build_model(arch)
    return model, model.init(jax.random.PRNGKey(0))


def _run_cfg(impl, kv_dtype):
    pol = (SoftmaxPolicy(impl=impl, precision="uint8")
           if impl != "exact" else SoftmaxPolicy())
    return RunConfig(dtype="float32", attention_backend="naive",
                     scan_layers=True, softmax_policy=pol,
                     kv_dtype=kv_dtype)


def _forced_logits(model, params, toks, impl, kv_dtype):
    """Teacher-forced per-step logits (B, S, V) through the lockstep
    path — bitwise the engine's datapath by the fuzz suite's
    engine≡lockstep pins, minus the cascade artifact."""
    out, _ = model.prefill(params, toks, _run_cfg(impl, kv_dtype),
                           max_len=64)
    return np.asarray(out)


@pytest.mark.parametrize("impl", sorted(POLICIES))
def test_exact_vs_int8_accuracy_budget(acc_lm, impl):
    """Acceptance: per policy, int8 KV stays inside the 1 % budget.

    Per-step logit max-abs / relative deltas are pinned
    (``LOGIT_BUDGETS``), and the net greedy-decision degradation — the
    drop in agreement with the exact-f32 gold stream, over steps whose
    f32 decision margin exceeds 1 % of the logit range (a margin below
    the int8 resolution floor is a coin flip, not a regression) — must
    stay under ``DEGRADATION_BUDGET``."""
    model, params = acc_lm
    bud = LOGIT_BUDGETS[impl]
    n_f32_right = n_int8_right = n_conf = n_steps = 0
    for seed in (0, 1, 2):
        rng = np.random.default_rng(seed)
        toks = jnp.asarray(rng.integers(0, VOCAB, size=(8, 48))
                           .astype(np.int32))
        gold = _forced_logits(model, params, toks, "exact",
                              "f32").argmax(-1)
        lf = _forced_logits(model, params, toks, impl, "f32")
        lq = _forced_logits(model, params, toks, impl, "int8")
        delta = np.abs(lf - lq)
        span = float(lf.max() - lf.min())
        assert delta.max() <= bud["max_abs"], \
            f"seed {seed}: logit max-abs delta {delta.max():.3f}"
        assert delta.max() / span <= bud["rel"], \
            f"seed {seed}: relative logit delta {delta.max() / span:.4f}"
        srt = np.sort(lf, -1)
        conf = (srt[..., -1] - srt[..., -2]) > 0.01 * span
        n_conf += int(conf.sum())
        n_steps += conf.size
        n_f32_right += int(((lf.argmax(-1) == gold) & conf).sum())
        n_int8_right += int(((lq.argmax(-1) == gold) & conf).sum())
    assert n_conf > 0.5 * n_steps  # the filter keeps most steps
    degradation = (n_f32_right - n_int8_right) / n_conf
    assert degradation < DEGRADATION_BUDGET, \
        f"{impl}: net degradation {degradation:.4f} over {n_conf} steps"


def test_engine_exact_vs_int8_first_tokens(acc_lm):
    """Engine-level closure of the budget: on confident prompts (f32
    first-step margin above the int8 floor) the real f32 and int8
    engines emit identical first tokens — single-step, so the greedy
    cascade cannot launder one hairline flip into a long mismatch."""
    model, params = acc_lm
    rng = np.random.default_rng(7)
    prompts = rng.integers(0, VOCAB, size=(24, 12))
    toks = jnp.asarray(prompts.astype(np.int32))
    lf = _forced_logits(model, params, toks, "exact", "f32")[:, -1]
    srt = np.sort(lf, -1)
    span = float(lf.max() - lf.min())
    conf = (srt[..., -1] - srt[..., -2]) > 0.01 * span
    assert conf.sum() >= 12  # enough confident prompts to mean anything
    reqs = [dict(prompt=p.tolist(), max_new_tokens=1, temperature=0.0,
                 seed=i) for i, p in enumerate(prompts)]
    first = {}
    for kv in ("f32", "int8"):
        eng = ServingEngine(model, params, _run_cfg("exact", kv),
                            EngineConfig(n_slots=2, cache=_CACHE,
                                         prefill_chunk=4))
        out = eng.run([dict(r) for r in reqs])
        first[kv] = np.array([out[rid].tokens[0] for rid in sorted(out)])
    mismatches = int((first["f32"][conf] != first["int8"][conf]).sum())
    assert mismatches == 0, \
        f"{mismatches}/{int(conf.sum())} confident prompts flipped"


def test_engine_rejects_unknown_kv_dtype(acc_lm):
    model, params = acc_lm
    with pytest.raises(ValueError, match="kv_dtype"):
        ServingEngine(model, params, _run_cfg("exact", "f32"),
                      EngineConfig(n_slots=2, cache=_CACHE,
                                   kv_dtype="int4"))
