"""The `Family` of `models.py` for the program's `CausalLM` built from a
`nemotron_h` configuration, beside `models_lm.py`'s (whose `Program` it runs
under: a configuration names this module's `nemotronh` as its `program`). A
layer is one part: the program holds a Mamba layer's five input projections
as one kernel `w_in` = [z | x | B | C | dt], an attention layer's q | k | v as
one, an expert layer's squared-ReLU experts as up kernels beside down ones;
the reference keeps every projection apart (`xp`: `jax.numpy` for trees on
the device, `numpy` for trees kept on the host)."""

from __future__ import annotations

import jax.numpy as jnp

from benchmark import models
from benchmark.models_lm import _build

# the Mamba mixer's weights: (program, reference) names
MAMBA = (("conv_taps", "conv_w"), ("conv_bias", "conv_b"),
         ("dt_bias", "dt_bias"), ("a_log", "A_log"), ("d_skip", "D"),
         ("norm", "gate_norm"), ("w_out", "w_out"))
MAMBA_IN = ("w_z", "w_x", "w_b", "w_c", "w_dt")
EXPERTS = ("router", "shared_w_up", "shared_w_down", "experts_w_up",
           "experts_w_down")


def _norm_key(blk) -> str:
    return "pre_mlp_norm" if blk.mixer is None else "in_norm"


def _to_program(w, model, xp=jnp):
    tree = {model.embed.name: {"embeddings": w["embed"]},
            model.final_norm.name: {"gain": w["final_norm"]},
            model.head.name: {"kernel": w["head"]}}
    for p, blk in zip(w["layers"], model.blocks):
        t = {_norm_key(blk): {"gain": p["norm"]}}
        if blk.mixer is None:
            t["mlp"] = {"router": p["router"],
                        "shared_w_up": p["shared"]["w_up"],
                        "shared_w_down": p["shared"]["w_down"],
                        "experts_w_up": p["experts"]["w_up"],
                        "experts_w_down": p["experts"]["w_down"]}
        elif blk.attn is None:
            t["mamba"] = {mine: p[theirs] for mine, theirs in MAMBA}
            t["mamba"]["w_in"] = xp.concatenate([p[k] for k in MAMBA_IN],
                                                axis=1)
        else:
            t["attn"] = {"w_in": xp.concatenate([p["wq"], p["wk"], p["wv"]],
                                                axis=1), "w_out": p["wo"]}
        tree[blk.name] = t
    return tree


def _from_program(tree, model, xp=jnp):
    layers = []
    for blk in model.blocks:
        t = tree[blk.name]
        p = {"norm": t[_norm_key(blk)]["gain"]}
        if blk.mixer is None:
            m = t["mlp"]
            p.update(router=m["router"],
                     shared={"w_up": m["shared_w_up"],
                             "w_down": m["shared_w_down"]},
                     experts={"w_up": m["experts_w_up"],
                              "w_down": m["experts_w_down"]})
        elif blk.attn is None:
            mx = blk.mixer
            gn = mx.n_groups * mx.state_dim
            cut = [mx.inner, 2 * mx.inner, 2 * mx.inner + gn,
                   2 * mx.inner + 2 * gn]
            p.update({theirs: t["mamba"][mine] for mine, theirs in MAMBA})
            p.update(zip(MAMBA_IN, xp.split(t["mamba"]["w_in"], cut, axis=1)))
        else:
            a = blk.attn
            q, kv = a.n_head * a.head_dim, a.n_kv_head * a.head_dim
            wq, wk, wv = xp.split(t["attn"]["w_in"], [q, q + kv], axis=1)
            p.update(wq=wq, wk=wk, wv=wv, wo=t["attn"]["w_out"])
        layers.append(p)
    return {"embed": tree[model.embed.name]["embeddings"], "layers": layers,
            "final_norm": tree[model.final_norm.name]["gain"],
            "head": tree[model.head.name]["kernel"]}


nemotronh = models.Family(_build, _to_program, _from_program)
