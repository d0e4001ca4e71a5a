"""The traffic generator: reproducible by seed, sizes as the mix states."""

import numpy as np
import pytest

from bench import cells as C
from bench import traffic

#: a documents mix (no cell runs one yet): 8 resident documents of
#: 8k-16k tokens, questions on them, a quarter bringing a new document
DOCUMENTS = {
    "kind": "open_loop", "n_slots": 8, "max_context": 16768,
    "rate_rps": 0.5, "preroll_s": 30,
    "documents": {"resident": 8, "dist": "loguniform", "min": 8192,
                  "max": 16384, "new_share": 0.25},
    "prompt": {"dist": "uniform", "min": 32, "max": 256},
    "output": {"dist": "uniform", "min": 16, "max": 128},
    "temperature": 0.7, "greedy_share": 0.5, "check": {"requests": 8},
}
MIXES = ["chat-short", "documents", "batch"]


def mix(name):
    if name == "documents":
        return dict(DOCUMENTS)
    return traffic.load(C.BENCH / "traffic" / f"{name}.json")


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a = traffic.plan(mix(name), 2 ** 33 + 5, 30, 1000)
    b = traffic.plan(mix(name), 2 ** 33 + 5, 30, 1000)
    assert len(a.requests) == len(b.requests)
    for x, y in zip(a.requests, b.requests):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.t, x.max_new, x.temperature, x.seed) == \
            (y.t, y.max_new, y.temperature, y.seed)
    for x, y in zip(a.docs, b.docs):
        assert np.array_equal(x, y)


@pytest.mark.parametrize("name", MIXES)
def test_seeds_reorder_the_same_work(name):
    m = mix(name)
    a = traffic.plan(m, 1, 30, 1000)
    b = traffic.plan(m, 2 ** 32 + 1, 30, 1000)
    if "documents" not in m:
        assert sorted(len(r.prompt) for r in a.requests) == \
            sorted(len(r.prompt) for r in b.requests)
    assert sorted(r.max_new for r in a.requests) == \
        sorted(r.max_new for r in b.requests)
    assert [r.max_new for r in a.requests] != [r.max_new for r in b.requests]
    if m["kind"] == "open_loop":
        end = m["preroll_s"] + 30
        ga = np.diff([r.t for r in a.requests] + [30])
        gb = np.diff([r.t for r in b.requests] + [30])
        assert np.allclose(sorted(ga), sorted(gb))
        assert np.isclose(ga.sum(), end)


@pytest.mark.parametrize("name", MIXES)
def test_sizes_match_clips_and_medians(name):
    m = mix(name)
    p = traffic.plan(m, 7, 51, 1000)
    outs = np.array([r.max_new for r in p.requests])
    assert outs.min() >= m["output"]["min"] and \
        outs.max() <= m["output"]["max"]
    if "documents" in m:
        d = m["documents"]
        for doc in p.docs:
            assert d["min"] <= len(doc) <= d["max"]
    q = np.array([len(r.prompt) for r in p.requests])
    if "documents" not in m:
        assert q.min() >= m["prompt"]["min"] and q.max() <= m["prompt"]["max"]
        if m["prompt"]["dist"] == "lognormal":
            assert abs(np.median(q) / m["prompt"]["median"] - 1) < 0.1
    if m["output"]["dist"] == "lognormal":
        assert abs(np.median(outs) / m["output"]["median"] - 1) < 0.1
    greedy = np.mean([r.greedy for r in p.requests])
    assert abs(greedy - m["greedy_share"]) <= 1 / len(p.requests)
    for r in p.requests:
        assert len(r.prompt) + r.max_new <= m["max_context"]


def test_open_loop_arrivals_start_at_the_preroll():
    m = mix("chat-short")
    p = traffic.plan(m, 3, 30, 1000)
    t = np.array([r.t for r in p.requests])
    assert t[0] == -m["preroll_s"]
    assert np.all(np.diff(t) > 0)
    assert len(t) == round(m["rate_rps"] * (m["preroll_s"] + 30))


def test_documents_are_asked_again_and_replaced():
    m = mix("documents")
    p = traffic.plan(m, 11, 51, 1000)
    docs = [r.doc for r in p.requests]
    n_res = m["documents"]["resident"]
    new = sum(d >= n_res for d in set(docs))
    assert new == round(m["documents"]["new_share"] * len(docs))
    assert len(docs) > len(set(docs))     # documents are asked again
    for r in p.requests:
        base = p.docs[r.doc] if r.doc < n_res else None
        if base is not None:
            assert np.array_equal(r.prompt[:len(base)], base)
