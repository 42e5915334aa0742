"""Build the program's model for a configuration and pour the benchmark's
own weights into it. A configuration names its `Family` here (or in a module
a later PR adds) under `program`; everything the program is asked for by name
sits in such a module, so the references stay free of it."""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from benchmark import cells


class Family(NamedTuple):
    """How one kind of model is built from a configuration, and how the
    reference's weight tree maps onto the program's and back."""
    build: Callable            # (cfg) -> the program's model
    to_program: Callable       # (reference tree, model) -> program tree
    from_program: Callable     # (program tree, model) -> reference tree


def key_of(seed: int):
    """A PRNG key from any non-negative whole seed (more than 32 bits too)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, seed >> 31)


def reference(cfg: dict):
    return cells.load(cfg["reference"])


# -- bert ---------------------------------------------------------------

def _build_bert(cfg):
    from analytics_zoo_tpu.tfpark.bert import BERTClassifierNet

    return BERTClassifierNet(
        num_classes=cfg["num_labels"], vocab=cfg["vocab_size"],
        hidden_size=cfg["hidden_size"], n_block=cfg["num_hidden_layers"],
        n_head=cfg["num_attention_heads"],
        seq_len=cfg["max_position_embeddings"],
        intermediate_size=cfg["intermediate_size"],
        hidden_drop=cfg["hidden_dropout_prob"],
        attn_drop=cfg["attention_probs_dropout_prob"])


def _bert_to_program(w, model):
    bert = {}
    for p, blk in zip(w["layers"], model.bert.blocks):
        bert[blk.name] = {
            "qkv_kernel": jnp.concatenate([p["wq"], p["wk"], p["wv"]], axis=1),
            "qkv_bias": jnp.concatenate([p["bq"], p["bk"], p["bv"]]),
            "proj_kernel": p["wo"], "proj_bias": p["bo"],
            "ln1_gamma": p["ln1"]["g"], "ln1_beta": p["ln1"]["b"],
            "ffn_in_kernel": p["w1"], "ffn_in_bias": p["b1"],
            "ffn_out_kernel": p["w2"], "ffn_out_bias": p["b2"],
            "ln2_gamma": p["ln2"]["g"], "ln2_beta": p["ln2"]["b"]}
    bert.update(word_embed=w["word"], pos_embed=w["pos"], type_embed=w["type"],
                embed_ln_gamma=w["emb_ln"]["g"], embed_ln_beta=w["emb_ln"]["b"],
                pooler_kernel=w["pool"]["w"], pooler_bias=w["pool"]["b"])
    return {model.bert.name: bert,
            model.head.name: {"kernel": w["cls"]["w"], "bias": w["cls"]["b"]}}


def _bert_from_program(tree, model):
    bert, layers = tree[model.bert.name], []
    for blk in model.bert.blocks:
        p = bert[blk.name]
        wq, wk, wv = jnp.split(p["qkv_kernel"], 3, axis=1)
        bq, bk, bv = jnp.split(p["qkv_bias"], 3)
        layers.append({
            "wq": wq, "bq": bq, "wk": wk, "bk": bk, "wv": wv, "bv": bv,
            "wo": p["proj_kernel"], "bo": p["proj_bias"],
            "ln1": {"g": p["ln1_gamma"], "b": p["ln1_beta"]},
            "w1": p["ffn_in_kernel"], "b1": p["ffn_in_bias"],
            "w2": p["ffn_out_kernel"], "b2": p["ffn_out_bias"],
            "ln2": {"g": p["ln2_gamma"], "b": p["ln2_beta"]}})
    head = tree[model.head.name]
    return {"word": bert["word_embed"], "pos": bert["pos_embed"],
            "type": bert["type_embed"],
            "emb_ln": {"g": bert["embed_ln_gamma"], "b": bert["embed_ln_beta"]},
            "layers": layers,
            "pool": {"w": bert["pooler_kernel"], "b": bert["pooler_bias"]},
            "cls": {"w": head["kernel"], "b": head["bias"]}}


# -- resnet50 -----------------------------------------------------------

def _build_resnet50(cfg):
    from analytics_zoo_tpu.models.image.imageclassification import resnet_50

    if (cfg["stem_width"], cfg["stage_widths"], cfg["stage_blocks"]) != (
            64, [64, 128, 256, 512], [3, 4, 6, 3]):
        raise ValueError("the program's resnet_50 has the published widths "
                         "and depths only")
    size = cfg["image_size"]
    return resnet_50(num_classes=cfg["num_labels"],
                     input_shape=(size, size, cfg["num_channels"]))


def _resnet_names(w):
    """(program layer prefix, reference conv+bn) pairs."""
    yield "stem", w["stem"]
    for si, blocks in enumerate(w["stages"]):
        for bi, blk in enumerate(blocks):
            for part, p in blk.items():
                yield f"res{si + 2}{chr(ord('a') + bi)}_{part}", p


def _resnet_to_program(w, model):
    tree = {"fc1000": {"kernel": w["fc"]["w"], "bias": w["fc"]["b"]}}
    for prefix, p in _resnet_names(w):
        tree[f"{prefix}_conv"] = {"kernel": p["k"]}
        tree[f"{prefix}_bn"] = {"gamma": p["g"], "beta": p["b"]}
    return tree


def _resnet_from_program(tree, model):
    def cb(prefix):
        return {"k": tree[f"{prefix}_conv"]["kernel"],
                "g": tree[f"{prefix}_bn"]["gamma"],
                "b": tree[f"{prefix}_bn"]["beta"]}

    stages, names = [], sorted(
        {n.rsplit("_", 2)[0] for n in tree if n.startswith("res")})
    for si in sorted({n[3] for n in names}):
        blocks = []
        for n in (m for m in names if m[3] == si):
            blocks.append({part: cb(f"{n}_{part}")
                           for part in ("a", "b", "c", "proj")
                           if f"{n}_{part}_conv" in tree})
        stages.append(blocks)
    return {"stem": cb("stem"), "stages": stages,
            "fc": {"w": tree["fc1000"]["kernel"], "b": tree["fc1000"]["bias"]}}


bert = Family(_build_bert, _bert_to_program, _bert_from_program)
resnet50 = Family(_build_resnet50, _resnet_to_program, _resnet_from_program)


class Program:
    """The system under test for one configuration: the program's model,
    compiled with the stated optimizer and loss, holding seeded weights."""

    def __init__(self, cfg: dict, seed: int, train: bool = True):
        build, self._to, self._from = cells.load(cfg["program"])
        self.cfg, self.ref = cfg, reference(cfg)
        self.model = build(cfg)
        if train:    # a served model gets no optimizer, so no moments
            opt = cfg["optimizer"]
            self.model.compile(
                optimizer=cells.load(opt["program"])(**opt["args"]),
                loss="sparse_categorical_crossentropy")
            self.criterion = self.model.criterion
        self.est = self.model._get_estimator()
        # all weights on the device in one jitted call from the seed
        self.model.set_weights(jax.jit(
            lambda k: self._to(self.ref.init_weights(cfg, k), self.model))(
                key_of(seed)))

    def to_reference_layout(self, tree):
        return self._from(tree, self.model)


def reference_weights(cfg: dict, seed: int):
    """The same numbers again, in the reference's layout."""
    return jax.jit(lambda k: reference(cfg).init_weights(cfg, k))(key_of(seed))
