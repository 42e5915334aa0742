"""The `Family` of `models.py` for the program's `CausalLM` built from a
`mellum` configuration, beside `models_lm.py`'s (whose `Program` it runs
under: a configuration names this module's `mellum2` as its `program`). The
program fuses q | k | v into one kernel (no gate) and an expert's gate | up
into one; the reference keeps every projection apart (`xp`: `jax.numpy` for
trees on the device, `numpy` for trees kept on the host)."""

from __future__ import annotations

import jax.numpy as jnp

from benchmark import models
from benchmark.models_lm import _build, _join, _split

NORMS = ("in_norm", "pre_mlp_norm")


def _to_program(w, model, xp=jnp):
    tree = {model.embed.name: {"embeddings": w["embed"]},
            model.final_norm.name: {"gain": w["final_norm"]},
            model.head.name: {"kernel": w["head"]}}
    for p, blk in zip(w["layers"], model.blocks):
        t = {n: {"gain": p[n]} for n in NORMS}
        t["attn"] = {"w_in": xp.concatenate([p["wq"], p["wk"], p["wv"]],
                                            axis=1), "w_out": p["wo"],
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
        t, a = tree[blk.name], blk.attn
        q, kv = a.n_head * a.head_dim, a.n_kv_head * a.head_dim
        wq, wk, wv = xp.split(t["attn"]["w_in"], [q, q + kv], axis=1)
        p = {n: t[n]["gain"] for n in NORMS}
        p.update(wq=wq, wk=wk, wv=wv, wo=t["attn"]["w_out"],
                 q_norm=t["attn"]["q_norm"], k_norm=t["attn"]["k_norm"])
        m = t["mlp"]
        if "router" in m:
            p["router"] = m["router"]
            p["experts"] = _split(m["experts_w_gate_up"], m["experts_w_down"],
                                  xp)
        else:
            p["mlp"] = _split(m["w_gate_up"], m["w_down"], xp)
        layers.append(p)
    return {"embed": tree[model.embed.name]["embeddings"], "layers": layers,
            "final_norm": tree[model.final_norm.name]["gain"],
            "head": tree[model.head.name]["kernel"]}


mellum2 = models.Family(_build, _to_program, _from_program)
