"""The `Family` of `models.py` for the program's `CausalLM` built from an
`lfm2_moe` configuration, beside `models_lm.py`'s (whose `Program` it runs
under: a configuration names this module's `lfm2` as its `program`). The
reference keeps every projection apart; the program fuses the convolution's
B | C | X, attention's q | k | v and gate | up into one kernel each, ties the
head to the embedding (one leaf on both sides) and has no shared expert (`xp`:
`jax.numpy` for trees on the device, `numpy` for trees kept on the host)."""

from __future__ import annotations

import jax.numpy as jnp

from benchmark import models
from benchmark.models_lm import _build, _join, _split

NORMS = {"in_norm": "operator_norm", "pre_mlp_norm": "ffn_norm"}


def _to_program(w, model, xp=jnp):
    tree = {model.embed.name: {"embeddings": w["embed"]},
            model.final_norm.name: {"gain": w["final_norm"]}}
    if "head" in w:
        tree[model.head.name] = {"kernel": w["head"]}
    for p, blk in zip(w["layers"], model.blocks):
        t = {mine: {"gain": p[theirs]} for mine, theirs in NORMS.items()}
        if "conv" in p:
            c = p["conv"]
            t["conv"] = {"w_in": xp.concatenate(
                [c["w_b"], c["w_c"], c["w_x"]], axis=1),
                "taps": c["taps"], "w_out": c["w_out"]}
        else:
            t["attn"] = {"w_in": xp.concatenate(
                [p["wq"], p["wk"], p["wv"]], axis=1), "w_out": p["wo"],
                "q_norm": p["q_norm"], "k_norm": p["k_norm"]}
        if "mlp" in p:
            t["mlp"] = {"w_gate_up": _join(p["mlp"], xp),
                        "w_down": p["mlp"]["w_down"]}
        else:
            t["mlp"] = {"router": p["router"],
                        "experts_w_gate_up": _join(p["experts"], xp),
                        "experts_w_down": p["experts"]["w_down"]}
        tree[blk.name] = t
    return tree


def _from_program(tree, model, xp=jnp):
    layers = []
    for blk in model.blocks:
        t = tree[blk.name]
        p = {theirs: t[mine]["gain"] for mine, theirs in NORMS.items()}
        if "conv" in t:
            w_b, w_c, w_x = xp.split(t["conv"]["w_in"], 3, axis=1)
            p["conv"] = {"w_b": w_b, "w_c": w_c, "w_x": w_x,
                         "taps": t["conv"]["taps"],
                         "w_out": t["conv"]["w_out"]}
        else:
            a = blk.attn
            q, kv = a.n_head * a.head_dim, a.n_kv_head * a.head_dim
            wq, wk, wv = xp.split(t["attn"]["w_in"], [q, q + kv], axis=1)
            p.update(wq=wq, wk=wk, wv=wv, wo=t["attn"]["w_out"],
                     q_norm=t["attn"]["q_norm"], k_norm=t["attn"]["k_norm"])
        m = t["mlp"]
        if "router" in m:
            p["router"] = m["router"]
            p["experts"] = _split(m["experts_w_gate_up"], m["experts_w_down"], xp)
        else:
            p["mlp"] = _split(m["w_gate_up"], m["w_down"], xp)
        layers.append(p)
    out = {"embed": tree[model.embed.name]["embeddings"], "layers": layers,
           "final_norm": tree[model.final_norm.name]["gain"]}
    if model.head is not None:
        out["head"] = tree[model.head.name]["kernel"]
    return out


lfm2 = models.Family(_build, _to_program, _from_program)
