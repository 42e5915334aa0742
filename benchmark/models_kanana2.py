"""The `Family` of `models.py` for the program's `CausalLM` built from a
`deepseek_v3` configuration, beside `models_lm.py`'s (whose `Program` it runs
under: a configuration names this module's `kanana2` as its `program`). The
reference keeps every projection apart; the program holds latent attention's
as the published model does, a head's parts side by side: `w_q` = a head's
[nope | rope] columns, `w_kv_a` = [latent | the shared rotary key], `w_kv_b` =
a head's [key nope | value] columns; gate | up in one kernel, the shared
experts one SwiGLU (`xp`: `jax.numpy` for trees on the device, `numpy` for
trees kept on the host)."""

from __future__ import annotations

import jax.numpy as jnp

from benchmark import models
from benchmark.models_lm import _build, _join, _split

NORMS = {"in_norm": "attn_norm", "pre_mlp_norm": "ffn_norm"}


def _by_head(parts, heads: int, xp):
    """Matrices (rows, heads x width_i) -> (rows, heads x sum of widths): a
    head's columns of each part side by side."""
    rows = parts[0].shape[0]
    return xp.concatenate([p.reshape(rows, heads, -1) for p in parts],
                          axis=-1).reshape(rows, -1)


def _from_heads(joined, heads: int, first: int, xp):
    """`_by_head` of two parts, undone: the first `first` columns of every
    head, and the rest."""
    rows = joined.shape[0]
    a, b = xp.split(joined.reshape(rows, heads, -1), [first], axis=-1)
    return a.reshape(rows, -1), b.reshape(rows, -1)


def _to_program(w, model, xp=jnp):
    tree = {model.embed.name: {"embeddings": w["embed"]},
            model.final_norm.name: {"gain": w["final_norm"]},
            model.head.name: {"kernel": w["head"]}}
    for p, blk in zip(w["layers"], model.blocks):
        h = blk.attn.n_head
        t = {mine: {"gain": p[theirs]} for mine, theirs in NORMS.items()}
        t["attn"] = {
            "w_q": _by_head([p["wq_nope"], p["wq_rope"]], h, xp),
            "w_kv_a": xp.concatenate([p["w_c"], p["w_kr"]], axis=1),
            "kv_norm": p["kv_norm"],
            "w_kv_b": _by_head([p["wk_nope"], p["wv"]], h, xp),
            "w_out": p["wo"]}
        if "mlp" in p:
            t["mlp"] = {"w_gate_up": _join(p["mlp"], xp),
                        "w_down": p["mlp"]["w_down"]}
        else:
            t["mlp"] = {"router": p["router"],
                        "shared_w_gate_up": _join(p["shared"], xp),
                        "shared_w_down": p["shared"]["w_down"],
                        "experts_w_gate_up": _join(p["experts"], xp),
                        "experts_w_down": p["experts"]["w_down"]}
        tree[blk.name] = t
    return tree


def _from_program(tree, model, xp=jnp):
    layers = []
    for blk in model.blocks:
        t, a = tree[blk.name], blk.attn
        p = {theirs: t[mine]["gain"] for mine, theirs in NORMS.items()}
        wq_nope, wq_rope = _from_heads(t["attn"]["w_q"], a.n_head,
                                       a.qk_nope_dim, xp)
        w_c, w_kr = xp.split(t["attn"]["w_kv_a"], [a.kv_rank], axis=1)
        wk_nope, wv = _from_heads(t["attn"]["w_kv_b"], a.n_head,
                                  a.qk_nope_dim, xp)
        p.update(wq_nope=wq_nope, wq_rope=wq_rope, w_c=w_c, w_kr=w_kr,
                 kv_norm=t["attn"]["kv_norm"], wk_nope=wk_nope, wv=wv,
                 wo=t["attn"]["w_out"])
        m = t["mlp"]
        if "router" in m:
            p["router"] = m["router"]
            p["shared"] = _split(m["shared_w_gate_up"], m["shared_w_down"], xp)
            p["experts"] = _split(m["experts_w_gate_up"], m["experts_w_down"], xp)
        else:
            p["mlp"] = _split(m["w_gate_up"], m["w_down"], xp)
        layers.append(p)
    return {"embed": tree[model.embed.name]["embeddings"], "layers": layers,
            "final_norm": tree[model.final_norm.name]["gain"],
            "head": tree[model.head.name]["kernel"]}


kanana2 = models.Family(_build, _to_program, _from_program)
