"""The program's side of the dense architecture (``dense.py``): the
``ArchConfig`` the program builds, and the seeded weights in the
program's parameter tree, one period of one layer."""

from __future__ import annotations

from repro.configs.base import ArchConfig


def arch_config(cfg: dict, d: dict) -> ArchConfig:
    return ArchConfig(
        name=cfg["name"], family="dense", n_layers=d["layers"],
        d_model=d["hidden"], n_heads=d["heads"], n_kv_heads=d["kv_heads"],
        d_ff=d["ffn"], vocab_size=d["vocab"], head_dim=d["head_dim"],
        qk_norm=d["qk_norm"], rope_theta=d["rope_theta"],
        norm_eps=d["norm_eps"])


def tree(w: dict) -> dict:
    """``bench.weights.make_all``'s leaves in the program's tree."""
    top, lay = w["top"], w["layers"]["dense"]
    mixer = {k: lay[k] for k in ("wq", "wk", "wv", "wo")}
    if "q_norm" in lay:
        mixer["q_norm"] = {"scale": lay["q_norm"]}
        mixer["k_norm"] = {"scale": lay["k_norm"]}
    block = {"norm1": {"scale": lay["attn_norm"]}, "mixer": mixer,
             "norm2": {"scale": lay["mlp_norm"]},
             "ffn": {k: lay[k] for k in ("w_up", "w_down", "w_gate")}}
    return {"embed": {"table": top["embed"]}, "periods": [block],
            "final_norm": {"scale": top["final_norm"]},
            "head": {"w": top["head"]}}
