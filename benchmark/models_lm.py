"""The program's causal language model for a configuration, holding the
benchmark's own weights: the `Family` of `models.py` for `CausalLM`, and the
`Program` whose loss is the token cross-entropy. The reference keeps every
projection apart; the program fuses q | k | v | gate and gate | up into one
kernel each, so the maps join and split them (`xp`: `jax.numpy` for trees on
the device, `numpy` for trees kept on the host)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import cells, models

NORMS = ("in_norm", "post_attn_norm", "pre_mlp_norm", "post_mlp_norm")


def _build(cfg):
    from analytics_zoo_tpu.models.causal_lm import CausalLM

    return CausalLM.from_config(cfg, seq_len=cfg["seq_len"],
                                dtype=cfg["compute_dtype"])


def _join(p, xp):
    return xp.concatenate([p["w_gate"], p["w_up"]], axis=-1)


def _to_program(w, model, xp=jnp):
    tree = {model.embed.name: {"embeddings": w["embed"]},
            model.final_norm.name: {"gain": w["final_norm"]},
            model.head.name: {"kernel": w["head"]}}
    for p, blk in zip(w["layers"], model.blocks):
        t = {n: {"gain": p[n]} for n in NORMS}
        t["attn"] = {"w_in": xp.concatenate(
            [p["wq"], p["wk"], p["wv"], p["wg"]], axis=1), "w_out": p["wo"],
            "q_norm": p["q_norm"], "k_norm": p["k_norm"]}
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


def _split(gate_up, down, xp):
    gate, up = xp.split(gate_up, 2, axis=-1)
    return {"w_gate": gate, "w_up": up, "w_down": down}


def _from_program(tree, model, xp=jnp):
    layers = []
    for blk in model.blocks:
        t, a = tree[blk.name], blk.attn
        q, kv = a.n_head * a.head_dim, a.n_kv_head * a.head_dim
        wq, wk, wv, wg = xp.split(t["attn"]["w_in"], [q, q + kv, q + 2 * kv],
                                  axis=1)
        p = {n: t[n]["gain"] for n in NORMS}
        p.update(wq=wq, wk=wk, wv=wv, wg=wg, wo=t["attn"]["w_out"],
                 q_norm=t["attn"]["q_norm"], k_norm=t["attn"]["k_norm"])
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


trinity = models.Family(_build, _to_program, _from_program)


class Program:
    """The system under test: `CausalLM` compiled with the stated optimizer
    and the token cross-entropy, holding seeded weights."""

    def __init__(self, cfg: dict, seed: int):
        build, self._to, self._from = cells.load(cfg["program"])
        self.cfg, self.ref = cfg, models.reference(cfg)
        self.model = build(cfg)
        self.est = self.model._get_estimator()
        # all weights on the device in one jitted call from the seed, poured
        # in before the optimizer exists: compile() then builds its moments
        # for them, and no second copy of the parameters is ever held
        self.model.set_weights(jax.jit(
            lambda k: self._to(self.ref.init_weights(cfg, k), self.model))(
                models.key_of(seed)))
        opt = cfg["optimizer"]
        self.model.compile(optimizer=cells.load(opt["program"])(**opt["args"]),
                           loss="token_crossentropy_from_logits")
        self.criterion = self.model.criterion

    def to_reference_layout(self, tree, xp=jnp):
        return self._from(tree, self.model, xp)
