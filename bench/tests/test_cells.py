"""Cells are found by name, a new one is added by files alone, and the
benchmark refuses to run where it cannot measure."""

import hashlib
import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

from bench import cells as C

ROOT = C.ROOT


def run_py(root, *args, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, str(root / "bench" / "run.py"),
                           *args], cwd=root, env=env, capture_output=True,
                          text=True, timeout=600)


def copy_tree(tmp_path, with_program=True):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", root)
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    if with_program:
        (root / "src").symlink_to(ROOT / "src")
    return root


def test_every_cell_resolves():
    bench = C.benchmark()
    for w in bench["workloads"]:
        cell = C.cell(w["name"])
        assert cell.check["logit_gap_mean_limit"] > 0
        assert C.rehearsal(cell).check["logit_gap_mean_limit"] > 0
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(C.reader(m["name"]))


def add_mix(root):
    """A new mix, its check and a new per-layer metric, as files, and a
    cell of an existing configuration under that mix: a rehearsal of it
    is correct and reads the new metric."""
    mix = json.loads((root / "bench/traffic/chat-short.json").read_text())
    mix["rate_rps"] *= 2
    (root / "bench/traffic/chat-double.json").write_text(json.dumps(mix))
    (root / "bench/checks/qwen3-32b-tp8.chat-double.json").write_text(
        json.dumps({"logit_gap_mean_limit": 0.012,
                    "rehearsal": {"logit_gap_mean_limit": 0.001}}))
    (root / "bench/metrics/server.requests_done.py").write_text(
        "def read(r):\n    return float(sum(x.done for x in r.records))\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": "qwen3-32b-tp8.chat-double",
                               "config": "qwen3-32b-tp8",
                               "traffic": "chat-double", "chips": 1,
                               "why": "twice the rate"})
    bench["per_layer"].append({"name": "server.requests_done", "unit": "n",
                               "better": "higher", "source": "host_clock",
                               "layer": "server (runtime/server.py)",
                               "moves": "output_tok_s",
                               "workloads": ["qwen3-32b-tp8.chat-double"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    def check():
        cell = C.cell("qwen3-32b-tp8.chat-double", root=root)
        assert cell.mix["rate_rps"] == 2 * C.cell(
            "qwen3-32b-tp8.chat-short").mix["rate_rps"]
        assert [m["name"] for m in cell.per_layer][-1] == \
            "server.requests_done"
        out = run_py(root, "--workload", "qwen3-32b-tp8.chat-double",
                     "--seed", "3", "--seconds", "2", "--trace", "1",
                     "--rehearse")
        assert out.returncode == 0, out.stderr[-3000:]
        res = json.loads(out.stdout.strip().splitlines()[-1])
        assert res["correct"] is True
        assert list(res)[-1] == "checks"

    return check


#: an architecture of two layer kinds with different leaves: ``mlp``, a
#: ReLU MLP, and ``mix``, one square matrix.  It counts kv_len FLOPs of
#: attention in each ``mlp`` layer, and kv_len bytes in all.
TOY = """
import jax

from bench.reference import _mm, _rms

KINDS = ("mlp", "mix")
SEEN = []   # the kinds reference_layer ran, in order


def dims(cfg):
    return {"hidden": cfg["hidden_size"], "width": cfg["toy_width"],
            "vocab": cfg["vocab_size"], "norm_eps": cfg["rms_norm_eps"],
            "mlp_layers": layer_kinds(cfg).count("mlp")}


def layer_kinds(cfg):
    return [KINDS[l % 2] for l in range(cfg["num_hidden_layers"])]


def leaves(cfg):
    D, F, V = cfg["hidden_size"], cfg["toy_width"], cfg["vocab_size"]
    return {"top": {"embed": ((V, D), 1.0),
                    "final_norm": ((D,), ("const", 1.0)),
                    "head": ((D, V), D ** -0.5)},
            "layers": {"mlp": {"norm": ((D,), ("const", 1.0)),
                               "w_in": ((D, F), D ** -0.5),
                               "w_out": ((F, D), 0.1 * F ** -0.5)},
                       "mix": {"w_mix": ((D, D), 0.1 * D ** -0.5)}}}


def reference_layer(kind, x, w, dims, softmax, control):
    SEEN.append(kind)
    if kind == "mix":
        return x + _mm(x, w["w_mix"], control)
    h = _rms(x, w["norm"], dict(dims)["norm_eps"])
    return x + _mm(jax.nn.relu(_mm(h, w["w_in"], control)), w["w_out"],
                   control)


def decode_attention(kv_len, dims):
    return float(kv_len * dims["mlp_layers"]), float(kv_len)


def prefill_attention(q_start, n, dims):
    return float(n * dims["mlp_layers"]), float(n)


def matmul_flops_per_token(dims):
    return 4.0 * dims["hidden"] * dims["width"] * dims["mlp_layers"]


def head_flops(dims):
    return 2.0 * dims["hidden"] * dims["vocab"]


def rehearse(cfg):
    cfg.update(hidden_size=16, toy_width=8)
    return cfg
"""


def add_architecture(root):
    """A new architecture module, a configuration that names it, a mix,
    a check and a cell, as files: every generic part of the harness
    follows the new module's layer kinds."""
    (root / "bench/arch/toy.py").write_text(TOY)
    serving = json.loads(
        (root / "bench/configs/qwen3-32b-tp8.json").read_text())["serving"]
    cfg = {"name": "toy", "bench_arch": "toy", "hidden_size": 32,
           "toy_width": 64, "num_hidden_layers": 4, "vocab_size": 256,
           "rms_norm_eps": 1e-6, "serving": serving,
           "assumed": {"init": {"attn_gain": 1.0, "mlp_gain": 1.0}}}
    (root / "bench/configs/toy.json").write_text(json.dumps(cfg))
    mix = (root / "bench/traffic/chat-short.json").read_text()
    (root / "bench/traffic/toy-chat.json").write_text(mix)
    (root / "bench/checks/toy.toy-chat.json").write_text(
        json.dumps({"logit_gap_mean_limit": 0.01,
                    "rehearsal": {"logit_gap_mean_limit": 0.001}}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy", "source": "a toy",
                             "file": "bench/configs/toy.json",
                             "reduced": [], "why": "two layer kinds"})
    bench["workloads"].append({"name": "toy.toy-chat", "config": "toy",
                               "traffic": "toy-chat", "chips": 1,
                               "why": "a toy"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    def check():
        from bench import reference, work
        from bench import weights as W

        cell = C.cell("toy.toy-chat", root=root)
        toy, cfg = cell.arch, cell.cfg
        assert toy.__file__ == str(root / "bench/arch/toy.py")
        kinds = ["mlp", "mix", "mlp", "mix"]
        assert toy.layer_kinds(cfg) == kinds
        # each kind's leaves over that kind's layers, and one layer's
        # leaves alone bitwise the same
        w = W.make_all(toy, cfg, 9)
        assert w["layers"]["mlp"]["w_in"].shape == (2, 32, 64)
        assert w["layers"]["mix"]["w_mix"].shape == (2, 32, 32)
        assert set(w["layers"]["mix"]) == {"w_mix"}
        for layer, kind in enumerate(kinds):
            one = W.make_layer(toy, cfg, 9, layer)
            assert set(one) == set(w["layers"][kind])
            for name, leaf in one.items():
                assert np.array_equal(
                    np.asarray(w["layers"][kind][name][layer // 2]),
                    np.asarray(leaf))
        logits = reference.forward(toy, cfg, 9, [np.arange(20)],
                                   [np.array([3, 19])])
        assert logits[0].shape == (2, 256)
        assert np.isfinite(logits[0]).all()
        assert toy.SEEN == kinds
        # the work sums are the module's, in the window only
        r = types.SimpleNamespace(arch=toy, dims=toy.dims(cfg),
                                  in_window=lambda t: t < 1.0,
                                  decode_rows=[(0.1, 10), (0.2, 30), (2, 5)],
                                  chunks=[(0.1, 0, 16), (3, 16, 8)])
        assert work.window_decode(r) == (2 * 40.0, 40.0, 2)
        assert work.window_prefill(r) == (2 * 16.0, 16.0, 1, 16)
        per_tok, head = 4.0 * 32 * 64 * 2, 2.0 * 32 * 256
        assert work.model_flops(r, "all") == (
            2 * 40.0 + 2 * (per_tok + head) + 2 * 16.0 + 16 * per_tok + head)
        # the rehearsal cuts the module's own widths
        small = C.rehearsal(cell)
        assert (small.cfg["hidden_size"], small.cfg["toy_width"]) == (16, 8)
        assert small.arch.layer_kinds(small.cfg) == ["mlp", "mix"]
        assert set(W.make_all(toy, small.cfg, 9)["layers"]) == {"mlp", "mix"}

    return check


def digests(root):
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*") if p.is_file()}


@pytest.mark.parametrize("add", [add_mix, add_architecture],
                         ids=["mix", "architecture"])
def test_a_cell_is_added_by_files_alone(tmp_path, add):
    root = copy_tree(tmp_path)
    before = digests(root)
    check = add(root)
    after = digests(root)
    assert {p: after[p] for p in before} == before   # none changed
    check()


def test_an_unknown_architecture_is_an_error(tmp_path):
    root = copy_tree(tmp_path, with_program=False)
    path = root / "bench/configs/qwen3-32b-tp8.json"
    path.write_text(path.read_text().replace('"bench_arch": "dense"',
                                             '"bench_arch": "dense2"'))
    with pytest.raises(KeyError, match=r"'dense2'.*have \['dense'\]"):
        C.cell("qwen3-32b-tp8.chat-short", root=root)


def test_no_tpu_no_result(tmp_path):
    out = run_py(ROOT, "--workload", "qwen3-32b-tp8.chat-short", "--seed",
                 "1", "--seconds", "1", "--trace", "0")
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not out.stdout.strip()


def test_benchmark_files_alone_give_no_result(tmp_path):
    root = copy_tree(tmp_path, with_program=False)
    out = run_py(root, "--workload", "qwen3-32b-tp8.chat-short", "--seed",
                 "1", "--seconds", "1", "--trace", "0", "--rehearse")
    assert out.returncode != 0
    assert not out.stdout.strip()


def test_unknown_workload_fails():
    with pytest.raises(KeyError):
        C.cell("no-such-cell")
