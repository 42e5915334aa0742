"""Causal decoder-only language model over the decoder block library
(keras/layers/decoder.py, keras/layers/moe.py): token embedding, a stack of
``DecoderBlock``s whose token mixer (``sliding_attention`` /
``full_attention`` / ``latent_attention`` / ``conv`` / ``mamba``) and
feed-forward kind (leading dense layers, then expert layers) come from the
configuration, or, a layer one part alone, the mixer or the expert layer
(``experts``), a final RMS norm and a head, its own or the embedding's
transpose.
``apply`` ends in logits over the vocabulary held here, in the compute type;
train it with ``compile(optimizer, loss="token_crossentropy_from_logits")``
and ``Estimator.train`` / ``fit`` like any other ``KerasNet``.

One chip of an expert-parallel group holds a share of each expert layer's
experts (``experts_held``) and a slice of the vocabulary (``vocab_size`` is
the slice; ids are drawn from it): see ``SparseMoE``.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.keras.engine.base import unique_name
from analytics_zoo_tpu.keras.engine.topology import KerasNet
from analytics_zoo_tpu.keras.layers.core import Dense
from analytics_zoo_tpu.keras.layers.decoder import (
    DecoderBlock, GatedShortConv, GroupedQueryAttention, LatentAttention,
    Mamba2Mixer, RMSNorm, SwiGLU, rms_norm,
)
from analytics_zoo_tpu.keras.layers.embeddings import Embedding
from analytics_zoo_tpu.keras.layers.moe import DECODER_INIT, SparseMoE
from analytics_zoo_tpu.ops.rotary import Rotary, Yarn


def _experts(cfg: Dict, held_key: str) -> Dict:
    """The experts of one chip's share: ``held_key`` counts those held here,
    numbered from ``experts_held_offset``, of the ``router_num_experts`` the
    router scores (where the file states no such width, all are held)."""
    held = cfg[held_key]
    return dict(n_experts=cfg.get("router_num_experts", held),
                experts_held=(cfg.get("experts_held_offset", 0), held))


def _listed_layers(cfg: Dict) -> Dict:
    """What ``afmoe`` and ``lfm2_moe`` name alike: a mixer a layer, the
    leading dense layers, grouped key-value heads, the experts held."""
    return dict(layer_types=cfg["layer_types"],
                num_dense_layers=cfg["num_dense_layers"],
                n_kv_head=cfg["num_key_value_heads"],
                **_experts(cfg, "num_experts"))


def _afmoe(cfg: Dict) -> Dict:
    """Trinity: four norms a layer, a gated attention whose full layers
    carry no position, one shared expert, a muP embedding, its own head."""
    return dict(
        **_listed_layers(cfg),
        head_dim=cfg["head_dim"], n_shared=cfg["num_shared_experts"],
        sliding_window=cfg["sliding_window"], rope_theta=cfg["rope_theta"],
        epsilon=cfg["rms_norm_eps"],
        embed_scale=(math.sqrt(cfg["hidden_size"])
                     if cfg.get("mup_enabled") else 1.0),
        route_norm=cfg["route_norm"], route_scale=cfg["route_scale"],
        bias_rate=cfg["load_balance_coeff"])


def _lfm2_moe(cfg: Dict) -> Dict:
    """LFM2: gated short convolutions beside full attention with rotary
    positions, the two pre-norms alone, no attention gate, no shared
    expert, a tied head (``tie_embeddings``, the family's convention
    where the file does not say). ``bias_rate`` is the training
    framework's, not the model file's: 0 leaves the bias at rest."""
    return dict(
        **_listed_layers(cfg),
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        n_shared=0, rope_theta=cfg["rope_parameters"]["rope_theta"],
        full_rotary=cfg["rope_parameters"]["rope_theta"],
        gated_attention=False,
        conv_kernel=cfg["conv_L_cache"], norms="pre",
        epsilon=cfg["norm_eps"], embed_scale=1.0,
        route_norm=cfg["norm_topk_prob"],
        route_scale=cfg["routed_scaling_factor"], route_eps=1e-6,
        bias_rate=(cfg.get("bias_rate", 0.001)
                   if cfg["use_expert_bias"] else 0.0),
        tie_embeddings=cfg.get("tie_embeddings", True))


# what `_deepseek_v3` does not build, by key: the only value it takes
_DEEPSEEK_V3_ONLY = {"q_lora_rank": None, "n_group": 1, "topk_group": 1,
                     "rope_scaling": None, "rope_interleave": True,
                     "scoring_func": "sigmoid"}


def _deepseek_v3(cfg: Dict) -> Dict:
    """DeepSeek-V3's layout without query compression (Kanana-2): every layer
    mixes by latent attention (no ``layer_types``: ``num_hidden_layers`` of
    them), the two pre-norms alone, ``first_k_dense_replace`` leading dense
    layers, then sigmoid top-k expert layers with a selection bias
    (``noaux_tc``, one group) and ``n_shared_experts`` shared experts;
    ``n_routed_experts`` counts the experts held here. ``bias_rate`` is the
    training framework's, as for LFM2. Raises for what is not built: a
    compressed query, grouped routing, scaled or half-rotated rotary,
    softmax scores."""
    for key, only in _DEEPSEEK_V3_ONLY.items():
        if cfg.get(key, only) != only:
            raise NotImplementedError(
                f"deepseek_v3 with {key}={cfg[key]!r}: only {only!r} is built")
    return dict(
        layer_types=["latent_attention"] * cfg["num_hidden_layers"],
        num_dense_layers=cfg["first_k_dense_replace"],
        n_kv_head=cfg["num_key_value_heads"],
        head_dim=cfg["qk_rope_head_dim"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"], kv_rank=cfg["kv_lora_rank"],
        rope_theta=cfg["rope_theta"], epsilon=cfg["rms_norm_eps"],
        **_experts(cfg, "n_routed_experts"),
        n_shared=cfg["n_shared_experts"], norms="pre", embed_scale=1.0,
        route_norm=cfg["norm_topk_prob"],
        route_scale=cfg["routed_scaling_factor"], route_eps=1e-20,
        bias_rate=cfg.get("bias_rate", 0.001),
        tie_embeddings=cfg.get("tie_word_embeddings", False))


# what `_nemotron_h` does not build, by key: the only value it takes
_NEMOTRON_H_ONLY = {"mamba_proj_bias": False, "mlp_bias": False,
                    "attention_bias": False, "use_bias": False,
                    "use_conv_bias": True, "residual_in_fp32": False,
                    "n_group": 1, "topk_group": 1, "mlp_hidden_act": "relu2",
                    "mamba_hidden_act": "silu", "sliding_window": None}
# a letter of `hybrid_override_pattern` -> the layer kind; `-` (a dense
# feed-forward alone) is not built
_NEMOTRON_H_LETTERS = {"M": "mamba", "*": "full_attention", "E": "experts"}


def _nemotron_h(cfg: Dict) -> Dict:
    """Nemotron-H: each layer ONE part behind one pre-norm, by the letters
    of ``hybrid_override_pattern`` (``M`` a Mamba-2 mixer, ``*`` grouped-query
    attention with no position, no q/k norm and no gate, ``E`` an expert
    layer of squared-ReLU experts beside one shared expert
    ``moe_shared_expert_intermediate_size`` wide, sigmoid top-k with a
    selection bias); ``n_routed_experts`` counts the experts held here.
    ``rope_theta`` and ``partial_rotary_factor`` are in the file and unused:
    the family's attention carries no position. The Mamba output projection
    starts scaled by 1 / sqrt(``rescale_prenorm_residual_layers``) (the
    published depth where the file is cut; ``num_hidden_layers`` where
    not said) under ``rescale_prenorm_residual``. ``bias_rate`` is the
    training framework's, as for LFM2. Raises for what is not built: a
    dense layer (``-``), a bias anywhere but the convolution's, a clamped
    time step, float32 residuals, grouped routing, another activation."""
    for key, only in _NEMOTRON_H_ONLY.items():
        if cfg.get(key, only) != only:
            raise NotImplementedError(
                f"nemotron_h with {key}={cfg[key]!r}: only {only!r} is built")
    lo, hi = cfg.get("time_step_limit", (0.0, None))
    if lo != 0 or hi not in (None, float("inf")):
        raise NotImplementedError(
            f"nemotron_h with time_step_limit={cfg['time_step_limit']!r}: "
            f"only [0, null] (no clamp) is built")
    pattern = cfg["hybrid_override_pattern"]
    unknown = sorted(set(pattern) - set(_NEMOTRON_H_LETTERS))
    if unknown:
        raise NotImplementedError(
            f"nemotron_h layers {unknown} in the pattern: only "
            f"{sorted(_NEMOTRON_H_LETTERS)} are built")
    if len(pattern) != cfg["num_hidden_layers"]:
        raise ValueError(f"a pattern of {len(pattern)} layers for "
                         f"num_hidden_layers={cfg['num_hidden_layers']}")
    layers = cfg.get("rescale_prenorm_residual_layers",
                     cfg["num_hidden_layers"])
    return dict(
        layer_types=[_NEMOTRON_H_LETTERS[c] for c in pattern],
        one_part=True, n_kv_head=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], gated_attention=False, qk_norm=False,
        norms="pre",
        epsilon=cfg["layer_norm_epsilon"], embed_scale=1.0,
        mamba_heads=cfg["mamba_num_heads"],
        mamba_head_dim=cfg["mamba_head_dim"], ssm_groups=cfg["n_groups"],
        ssm_state=cfg["ssm_state_size"], conv_kernel=cfg["conv_kernel"],
        ssm_chunk=cfg["chunk_size"],
        time_step=(cfg["time_step_min"], cfg["time_step_max"],
                   cfg["time_step_floor"]),
        ssm_out_scale=(layers ** -0.5 if cfg["rescale_prenorm_residual"]
                       else 1.0),
        **_experts(cfg, "n_routed_experts"), expert_act="relu2",
        n_shared=cfg["n_shared_experts"],
        shared_width=cfg["moe_shared_expert_intermediate_size"],
        route_norm=cfg["norm_topk_prob"],
        route_scale=cfg["routed_scaling_factor"], route_eps=1e-20,
        bias_rate=cfg.get("bias_rate", 0.001),
        tie_embeddings=cfg.get("tie_word_embeddings", False))


# what `_mellum` does not build, by key: the only value it takes
_MELLUM_ONLY = {"use_sliding_window": True, "max_window_layers": 0,
                "attention_bias": False, "hidden_act": "silu"}
# a `rope_parameters` section's keys that `_mellum_rotary` reads
_ROPE_KEYS = {"rope_type", "rope_theta", "factor",
              "original_max_position_embeddings", "beta_fast", "beta_slow",
              "attention_factor"}


def _mellum_rotary(kind: str, section: Dict) -> Rotary:
    """The rotary spec of one layer kind's ``rope_parameters`` section:
    ``default`` (a base alone) or ``yarn``; anything else is refused."""
    rope_type = section.get("rope_type", "default")
    unknown = sorted(set(section) - _ROPE_KEYS)
    if rope_type not in ("default", "yarn") or unknown:
        raise NotImplementedError(
            f"mellum {kind} rotary {rope_type!r} with {unknown}: only "
            f"'default' and 'yarn' over {sorted(_ROPE_KEYS)} are built")
    if rope_type == "default":
        return Rotary(float(section["rope_theta"]))
    return Rotary(float(section["rope_theta"]), Yarn(
        float(section["factor"]),
        int(section["original_max_position_embeddings"]),
        float(section.get("beta_fast", 32.0)),
        float(section.get("beta_slow", 1.0)),
        section.get("attention_factor")))


def _mellum(cfg: Dict) -> Dict:
    """Mellum (JetBrains; the Qwen3-MoE layout): grouped-query attention
    whose ``layer_types`` windows (``sliding_window``) and full layers each
    take the rotary of their own ``rope_parameters`` section (YaRN on the
    full ones), q / k head norms, no gate, the two pre-norms alone; the
    leading ``dense`` entries of ``mlp_layer_types`` are dense layers, the
    ``sparse`` ones softmax top-k expert layers with no shared expert and no
    selection bias (``bias_rate`` 0), balanced by the loss's term of
    ``router_aux_loss_coef`` (0.001 where the file has none, the training
    framework's choice); ``num_experts`` counts the experts held here. With
    nothing steering the load, the held rows always go through in chunks
    (``SparseMoE.compact``).
    Raises for what is not built: no window, windows on some layers only,
    biases, another activation, a dense layer after an expert one, a rotary
    type other than ``default`` and ``yarn``."""
    for key, only in _MELLUM_ONLY.items():
        if cfg.get(key, only) != only:
            raise NotImplementedError(
                f"mellum with {key}={cfg[key]!r}: only {only!r} is built")
    mlp = cfg["mlp_layer_types"]
    dense = next((i for i, kind in enumerate(mlp) if kind != "dense"),
                 len(mlp))
    if set(mlp) - {"dense", "sparse"} or "dense" in mlp[dense:]:
        raise NotImplementedError(
            f"mellum mlp_layer_types {mlp}: only leading 'dense' layers "
            f"before 'sparse' ones are built")
    if len(mlp) != len(cfg["layer_types"]):
        raise ValueError(f"{len(mlp)} mlp_layer_types for "
                         f"{len(cfg['layer_types'])} layer_types")
    rope = {kind: _mellum_rotary(kind, section)
            for kind, section in cfg["rope_parameters"].items()}
    missing = sorted(set(cfg["layer_types"]) - set(rope))
    if missing:
        raise ValueError(f"mellum layers {missing} have no rope_parameters")
    return dict(
        layer_types=cfg["layer_types"], num_dense_layers=dense,
        n_kv_head=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        **_experts(cfg, "num_experts"), n_shared=0,
        sliding_window=cfg["sliding_window"],
        rope_theta=rope.get("sliding_attention"),
        full_rotary=rope.get("full_attention"), gated_attention=False,
        qk_norm=True, norms="pre", epsilon=cfg["rms_norm_eps"],
        embed_scale=1.0, route_norm=cfg["norm_topk_prob"], route_scale=1.0,
        route_eps=0.0, scores="softmax", bias_rate=0.0,
        balance_coef=cfg.get("router_aux_loss_coef", 0.001),
        tie_embeddings=cfg.get("tie_word_embeddings", False))


# a published config's `model_type` -> the constructor's arguments that its
# own key names give
_FAMILIES = {"afmoe": _afmoe, "lfm2_moe": _lfm2_moe,
             "deepseek_v3": _deepseek_v3, "nemotron_h": _nemotron_h,
             "mellum": _mellum}


class CausalLM(KerasNet):
    """See the module docstring. ``layer_types``: a layer's token mixer, one
    of ``"sliding_attention"`` (rotary positions by ``rope_theta``, a base or
    an ``ops.rotary.Rotary`` spec, window ``sliding_window``),
    ``"full_attention"`` (causal; rotary positions by ``full_rotary``, a
    base or a spec, where given, else no positional encoding),
    ``"latent_attention"`` (a
    ``LatentAttention`` of ``qk_nope_dim``, ``qk_rope_dim``, ``v_dim`` and
    ``kv_rank``, rotary over neighbouring pairs;
    ``n_kv_head`` and ``head_dim`` say nothing of it), ``"conv"`` (a
    ``GatedShortConv`` over ``conv_kernel`` tokens) or ``"mamba"`` (a
    ``Mamba2Mixer`` of ``mamba_heads`` heads of ``mamba_head_dim``,
    ``ssm_groups`` groups of B and C ``ssm_state`` wide, a convolution over
    ``conv_kernel`` tokens, scanned in chunks of ``ssm_chunk``); the first
    ``num_dense_layers`` layers get a dense ``SwiGLU`` of ``dense_width``,
    the others a ``SparseMoE`` of ``expert_act`` experts (``"swiglu"`` or
    ``"relu2"``) beside a shared one ``shared_width`` wide (``expert_width *
    n_shared`` where not given), routed by ``scores`` (``"sigmoid"`` with a
    selection bias moved by ``bias_rate``, or ``"softmax"``); with
    ``balance_coef`` > 0 the training loss gains the expert layers' balance
    term (``auxiliary_loss``). ``one_part``: each layer is one part alone,
    the kind's mixer, or, of kind ``"experts"``, the ``SparseMoE``.
    ``gated_attention``: the attention's sigmoid output gate; ``qk_norm``:
    its per-head RMS norm of q and k. ``norms``: a
    block's norm layout (``DecoderBlock``). ``embed_scale``: the embedding's
    multiplier (sqrt(hidden) with muP). ``tie_embeddings``: the head is the
    embedding's transpose, one leaf of the parameters whose gradient is the
    sum of both uses. State: each expert layer's selection bias, step
    counts and balance statistics, ``tokens``, the tokens of the last
    training step, and, where some layer has a window, ``window_pairs``, the
    (query, key) pairs inside the windows of that step summed over the
    window layers; ``train_stats`` hands them to ``Estimator.train``, which
    brings them out with the loss and gives them back to
    ``record_train_stats`` at the drain."""

    MIXERS = ("sliding_attention", "full_attention", "latent_attention",
              "conv", "mamba")
    EXPERTS_ALONE = "experts"

    def __init__(self, vocab_size: int, hidden_size: int,
                 layer_types: Sequence[str], n_head: int, n_kv_head: int,
                 head_dim: int, dense_width: int, num_dense_layers: int = 0,
                 n_experts: int = 0, experts_held: Optional[Tuple[int, int]] = None,
                 expert_width: int = 0, top_k: int = 1, n_shared: int = 1,
                 sliding_window: int = 2048,
                 rope_theta: Union[float, Rotary] = 10000.0,
                 epsilon: float = 1e-5, embed_scale: Optional[float] = None,
                 route_norm: bool = True, route_scale: float = 1.0,
                 bias_rate: float = 0.001, route_eps: float = 1e-20,
                 full_rotary: Union[None, float, Rotary] = None,
                 gated_attention: bool = True,
                 conv_kernel: int = 3, norms: str = "sandwich",
                 tie_embeddings: bool = False, qk_nope_dim: int = 0,
                 qk_rope_dim: int = 0, v_dim: int = 0, kv_rank: int = 0,
                 qk_norm: bool = True, one_part: bool = False,
                 mamba_heads: int = 0, mamba_head_dim: int = 0,
                 ssm_groups: int = 1, ssm_state: int = 0, ssm_chunk: int = 128,
                 time_step: Tuple[float, float, float] = (0.001, 0.1, 1e-4),
                 ssm_out_scale: float = 1.0, expert_act: str = "swiglu",
                 shared_width: Optional[int] = None, scores: str = "sigmoid",
                 balance_coef: float = 0.0,
                 seq_len: Optional[int] = None,
                 dtype: Optional[str] = "bfloat16", remat: bool = True,
                 name: Optional[str] = None):
        super().__init__(name or unique_name("causal_lm"))
        self.vocab_size, self.hidden_size = int(vocab_size), int(hidden_size)
        self.seq_len, self.epsilon = seq_len, epsilon
        self.embed_scale = (math.sqrt(hidden_size) if embed_scale is None
                            else float(embed_scale))
        self.dtype = None if dtype is None else jnp.dtype(dtype)
        self.embed = Embedding(vocab_size, hidden_size, init=DECODER_INIT,
                               name=self.name + "_embed")
        self.embed.ensure_built((None, seq_len))
        self.blocks = []
        kinds = self.MIXERS + ((self.EXPERTS_ALONE,) if one_part else ())
        for i, kind in enumerate(layer_types):
            if kind not in kinds:
                raise ValueError(f"layer {i}: unknown kind {kind!r}; known: "
                                 f"{list(kinds)}")
            sliding = kind == "sliding_attention"
            if kind == self.EXPERTS_ALONE:
                mixer = None
            elif kind == "mamba":
                mixer = Mamba2Mixer(
                    mamba_heads, mamba_head_dim, ssm_groups, ssm_state,
                    conv_kernel, ssm_chunk, time_step, ssm_out_scale, epsilon,
                    name=f"{self.name}_l{i}_mamba")
            elif kind == "conv":
                mixer = GatedShortConv(conv_kernel,
                                       name=f"{self.name}_l{i}_conv")
            elif kind == "latent_attention":
                mixer = LatentAttention(
                    n_head, qk_nope_dim, qk_rope_dim, v_dim, kv_rank,
                    rope_theta, epsilon, name=f"{self.name}_l{i}_attn")
            else:
                mixer = GroupedQueryAttention(
                    n_head, n_kv_head, head_dim,
                    window=sliding_window if sliding else None,
                    rope_theta=rope_theta if sliding else full_rotary,
                    qk_norm=qk_norm, gated=gated_attention, epsilon=epsilon,
                    name=f"{self.name}_l{i}_attn")
            if one_part and mixer is not None:
                mlp = None
            elif i < num_dense_layers:
                mlp = SwiGLU(dense_width, name=f"{self.name}_l{i}_mlp")
            else:
                mlp = SparseMoE(n_experts, expert_width, top_k, experts_held,
                                n_shared, route_norm, route_scale, bias_rate,
                                route_eps, expert_act, shared_width, scores,
                                balance_coef, name=f"{self.name}_l{i}_moe")
            block = DecoderBlock(mixer, mlp, epsilon, dtype, remat, norms,
                                 name=f"{self.name}_l{i}")
            block.ensure_built((None, seq_len, hidden_size))
            self.blocks.append(block)
        self.conv_layers = sum(kind == "conv" for kind in layer_types)
        self.latent_layers = sum(kind == "latent_attention"
                                 for kind in layer_types)
        self.ssm_layers = sum(kind == "mamba" for kind in layer_types)
        self.windows = [b.attn.window for b in self.blocks
                        if b.attn is not None
                        and getattr(b.attn, "window", None) is not None]
        self.balance_coef = float(balance_coef)
        self.final_norm = RMSNorm(epsilon, name=self.name + "_final_norm")
        self.final_norm.ensure_built((None, seq_len, hidden_size))
        self.head = None
        if not tie_embeddings:
            self.head = Dense(vocab_size, init=DECODER_INIT, bias=False,
                              name=self.name + "_head")
            self.head.ensure_built((None, seq_len, hidden_size))
        held = [b.mlp.experts_held for b in self.blocks if b.has_state]
        self.experts_held = held[0] if held else None
        self._obs = None

    @classmethod
    def from_config(cls, cfg: Dict, **kw) -> "CausalLM":
        """From a published config, by its ``model_type`` (``afmoe`` where it
        states none): the keys every family names alike here, each family's
        own in its function above, plus ``router_num_experts`` (the router's
        width) where the family's count of experts (``num_experts``,
        ``n_routed_experts``) counts only the experts held, from
        ``experts_held_offset``."""
        kind = cfg.get("model_type", "afmoe")
        if kind not in _FAMILIES:
            raise ValueError(f"unknown model_type {kind!r}; known: "
                             f"{sorted(_FAMILIES)}")
        shared = dict(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            n_head=cfg["num_attention_heads"],
            dense_width=cfg["intermediate_size"],
            expert_width=cfg["moe_intermediate_size"],
            top_k=cfg["num_experts_per_tok"])
        return cls(**shared, **_FAMILIES[kind](cfg), **kw)

    # -- model protocol --------------------------------------------------

    def layers(self):
        tail = [self.final_norm] + ([self.head] if self.head else [])
        return [self.embed, *self.blocks, *tail]

    def init(self, rng):
        params, state = super().init(rng)
        state[self.name] = {key: jnp.zeros((), jnp.float32)
                            for key in self._step_counts()}
        return params, state

    def _step_counts(self):
        """What the model's own state counts of a training step."""
        return ("tokens", "window_pairs") if self.windows else ("tokens",)

    def apply(self, params, state, x, training=False, rng=None):
        """x: token ids (rows, tokens) -> (logits (rows, tokens, vocabulary)
        in the compute type, the new state)."""
        ids = x[0] if isinstance(x, (list, tuple)) else x
        with jax.named_scope("lm.embed"):
            h = self.embed.call(params[self.embed.name], ids) * self.embed_scale
        new_state = dict(state)
        for block in self.blocks:
            if block.has_state:
                h, new_state[block.name] = block.call(
                    params[block.name], h, state=state.get(block.name),
                    training=training)
            else:
                h = block.call(params[block.name], h)
        with jax.named_scope("lm.head"):
            h = rms_norm(h, params[self.final_norm.name]["gain"], self.epsilon)
            if self.head is None:      # tied: the embedding's transpose
                kernel = params[self.embed.name]["embeddings"].T
            else:
                kernel = params[self.head.name]["kernel"]
            logits = h @ (kernel if self.dtype is None
                          else kernel.astype(self.dtype))
        if training:
            rows, seq = ids.shape
            # keys a query sees in a window of w, summed over a row: the
            # first w queries see 1 .. w, the others w each
            pairs = sum(w * (w + 1) / 2 + (seq - w) * w if w < seq
                        else seq * (seq + 1) / 2 for w in self.windows)
            counted = {"tokens": ids.size, "window_pairs": rows * pairs}
            new_state[self.name] = {
                key: jnp.asarray(counted[key], jnp.float32)
                for key in self._step_counts()}
        return logits, new_state

    def get_output_shape(self):
        return (None, self.seq_len, self.vocab_size)

    def get_input_shape(self):
        return (None, self.seq_len)

    # -- the loss's own term ----------------------------------------------

    def auxiliary_loss(self, model_state):
        """The term a training loss adds beside the criterion, from the state
        the step's ``apply`` returned: ``balance_coef * E * sum_e f_e p_e``,
        ``f_e`` the tokens that picked expert e over the tokens and ``p_e``
        its mean probability, each pooled over the expert layers (as
        ``load_balancing_loss_func`` of the transformers library pools a
        step's router logits), E the router's width. A constant 0 without
        ``balance_coef``."""
        if not self.balance_coef:
            return 0.0
        moe = [model_state[b.name] for b in self.blocks if b.has_state]
        with jax.named_scope("moe.balance"):
            f = jnp.mean(jnp.stack([m["balance_f"] for m in moe]), axis=0)
            p = jnp.mean(jnp.stack([m["balance_p"] for m in moe]), axis=0)
            return self.balance_coef * f.shape[0] * jnp.sum(f * p)

    # -- step statistics -------------------------------------------------

    def train_stats(self, model_state) -> Dict:
        """What a train step returns beside its loss: the step's tokens, its
        (query, key) pairs inside windows, its balance term and, a row an
        expert layer, the tokens routed to each expert and whether the held
        ones went through in the compacted pass."""
        stats = dict(model_state[self.name])
        moe = [model_state[b.name] for b in self.blocks if b.has_state]
        if moe:
            stats["expert_tokens"] = jnp.stack(
                [m["expert_tokens"] for m in moe])
            stats["moe_compact"] = jnp.stack([m["compact"] for m in moe])
        if self.balance_coef:
            stats["balance"] = self.auxiliary_loss(model_state)
        return stats

    def record_train_stats(self, stats: Dict) -> None:
        """One step's statistics, on the host at ``train.drain``, into the
        counters (``common.observability.lm_train_metrics``)."""
        if self._obs is None:
            from analytics_zoo_tpu.common.observability import lm_train_metrics

            self._obs = lm_train_metrics()
        obs = self._obs
        obs["tokens"].inc(float(stats["tokens"]))
        # the step's tokens went through every short convolution the model has
        obs["conv_token_layers"].inc(float(stats["tokens"]) * self.conv_layers)
        # and through every latent-attention layer
        obs["latent_token_layers"].inc(
            float(stats["tokens"]) * self.latent_layers)
        # and through every Mamba-2 layer
        obs["ssm_token_layers"].inc(float(stats["tokens"]) * self.ssm_layers)
        if "window_pairs" in stats:
            obs["window_pairs"].inc(float(stats["window_pairs"]))
        if "balance" in stats:
            obs["balance"].observe(float(stats["balance"]))
        counts = stats.get("expert_tokens")
        if counts is None:
            return
        obs["moe_calls"].inc(len(counts))
        obs["moe_calls_compact"].inc(float(np.sum(stats["moe_compact"])))
        lo, n = self.experts_held
        held = np.asarray(counts)[:, lo:lo + n]
        obs["assignments_held"].inc(float(held.sum()))
        obs["assignments_absent"].inc(float(np.sum(counts) - held.sum()))
        for layer in held:
            obs["load_max"].observe(float(layer.max()))
            obs["load_mean"].observe(float(layer.mean()))
